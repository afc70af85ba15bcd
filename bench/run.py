"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``.  Exits
non-zero, printing no result, unless the first device JAX sees is a TPU
and there are as many as the cell asks for.  The last line of standard
output is the result (``bench/harness.py``); ``--keep-trace PATH`` also
writes the reduced trace of a ``--trace 1`` run there (gzipped JSON).
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    try:
        harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_process=T_PROCESS, keep_trace=args.keep_trace)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
