"""Shared steering for the benchmark's CPU tests.

The benchmark gates on a TPU, keeps a compilation cache in the checkout,
reads the chip's peaks and runs the reference in worker processes; these
tests run it on the CPU at tiny sizes, so they steer all four from the
test side: a gate that passes the CPU, no persistent cache, a peaks entry
for the CPU and the reference in the test's own process.  ``shrink``
cuts a cell's traffic to a size the interpreter runs in seconds, by the
traffic's kind, as the harness reads the cell's files, so a cell a later
change adds is covered without a new test.
"""

import json
import os
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workloads() -> list:
    return [w["name"] for w in spec()["workloads"]]


def cpu_device(chips: int) -> dict:
    import jax

    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}


def steer(monkeypatch, tiny: bool = True) -> None:
    """Steer the harness onto the CPU; ``tiny`` shrinks every cell read."""
    monkeypatch.setattr(harness, "gate", cpu_device)
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "peaks", lambda kind, root=None: PEAKS)
    monkeypatch.setattr(harness, "POOL_SIZE", 1)
    if tiny:
        load_cell = harness.load_cell

        def load_tiny(*a, **k):
            cell, config, traffic = load_cell(*a, **k)
            shrink(config, traffic)
            return cell, config, traffic

        monkeypatch.setattr(harness, "load_cell", load_tiny)


def shrink(config: dict, traffic: dict) -> None:
    kind = traffic["kind"]
    if kind == "sweep":
        traffic["axes"] = {
            n: [v["linspace"][0], v["linspace"][1]] if isinstance(v, dict) else v[:2]
            for n, v in traffic["axes"].items()
        }
        traffic["replicas"] = 2
        traffic["sim_time"] = 300.0
        traffic["check"]["cells"] = 2
    elif kind == "online":
        traffic["service"]["n_bins"] = 4
        traffic["observe_seconds"] = 60.0
        traffic["max_requests"] = 40
        traffic["check"]["ticks"] = 4
    else:
        raise AssertionError(f"no tiny size for traffic kind {kind!r}")


def run_tiny(workload: str, capsys, seconds: float = 0.5, trace: bool = False,
             seed: int = 2**31 + 3, root: str = ROOT) -> tuple:
    """One run under ``steer``; returns ``(result, stdout_lines, stderr_lines)``."""
    result = harness.run(workload, seed, seconds, trace, t_process=time.perf_counter(), root=root)
    out, err = capsys.readouterr()
    return result, out.strip().splitlines(), err.strip().splitlines()
