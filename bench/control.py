"""A cell's program readings and its control, on several seeds.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

In one process, for each seed: build the cell's traffic, warm up, run a
window of ``--seconds`` at the cell's own load, then compare a sample of
its requests with the plain reference twice: as the program produced
them (the readings a limit must sit above) and with the reference
computed one precision below what the configuration states in the
program's place (the control, whose readings a limit must sit below).
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seeds: list, seconds: float, *, control_seeds: int = 3,
             root: str = ROOT) -> list:
    """``[{"seed", "program", "control"}]``: the compared numbers of the
    program on each seed, and of the control on the first
    ``control_seeds`` of them, from the checkout at ``root``."""
    import numpy as np

    import harness

    spec = harness.load_spec(root)
    cell, config, traffic = harness.load_cell(spec, workload, root)
    harness.gate(int(cell["chips"]))
    harness.enable_cache()
    sys.path.insert(0, os.path.join(root, "src"))
    import mixes

    # loaded before the workers start, so that its reference imports there
    kind = mixes.load_kind(traffic["kind"], root)
    control = mixes.control_precision(config, traffic)
    pool = harness.reference_pool()
    out = []
    try:
        for seed in seeds:
            mix = kind.Mix(config, traffic, seed)
            warm = int(traffic.get("warmup_requests", 1))
            mix.prepare(warm + int(traffic.get("max_requests", 0)))
            for i in range(warm):
                mix.request(i)
            done, lat, window_s, failed = harness._window(mix, warm, seconds, annotate=False)
            row = {"seed": seed, "control_precision": control, "requests": len(lat), "failed": failed}
            runs = [("program", None)] + ([("control", control)] if len(out) < control_seeds else [])
            for name, prec in runs:
                rng = np.random.default_rng(np.random.SeedSequence([int(seed), harness.CHECK_SALT]))
                row[name] = mix.check(done, rng, pool, control=prec)
            out.append(row)
            print(json.dumps(row), flush=True)
    finally:
        if pool is not None:
            pool.close()
            pool.join()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="A cell's program readings and its control.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--control-seeds", type=int, default=3,
                   help="run the control on this many of the seeds, the first ones")
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    try:
        readings(args.workload, [int(s) for s in args.seeds.split(",")], args.seconds,
                 control_seeds=args.control_seeds)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
