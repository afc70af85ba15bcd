"""Shared steering for the benchmark's CPU tests.

The benchmark gates on a TPU, keeps a compilation cache in the checkout,
reads the chip's peaks and runs the reference in worker processes; these
tests run it on the CPU at tiny sizes, so they steer all four from the
test side: a gate that passes the CPU, no persistent cache, a peaks entry
for the CPU and the reference in the test's own process.

What the tests need to know of a traffic kind sits in a file of its own,
``tests/bench/kinds/<kind>.py``, beside the kind's ``bench/kinds/<kind>.py``:

* ``tiny(config, traffic)`` cuts a cell's files, in place, to a size the
  interpreter runs in seconds;
* ``asked_work(config, traffic)`` is the work a request asks for, worked
  out from the two files alone;
* ``plant(monkeypatch, fault)`` breaks the timed path underneath where the
  kind's entry point makes its per-cell results, once for each of
  ``FAULTS``: a step that leaves its state unchanged, half of the
  replicas left out with the mean taken over the rest, and an answer
  altered where it is produced.

The per-cell tests read them by the kind of each cell, as the harness
reads the cell's files, so a cell a later change adds, of a kind old or
new, is covered without a new test.
"""

import dataclasses
import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
FAULTS = ("unchanged", "half", "altered")


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workloads() -> list:
    return [w["name"] for w in spec()["workloads"]]


def hooks_path(kind: str, root: str = ROOT) -> str:
    return os.path.join(root, "tests", "bench", "kinds", kind + ".py")


def hooks(kind: str, root: str = ROOT):
    """The test hooks of traffic kind ``kind``, ``tests/bench/kinds/<kind>.py``
    in the checkout at ``root``."""
    path = hooks_path(kind, root)
    if not os.path.isfile(path):
        raise AssertionError(
            f"traffic kind {kind!r} has no test hooks: add {os.path.relpath(path, root)} "
            "with tiny(config, traffic), asked_work(config, traffic) and "
            "plant(monkeypatch, fault)"
        )
    mod_spec = importlib.util.spec_from_file_location("bench_test_kind_" + kind, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def kind_of(workload: str, root: str = ROOT) -> str:
    _, _, traffic = harness.load_cell(harness.load_spec(root), workload, root)
    return traffic["kind"]


def hooked_workloads() -> list:
    """The cells whose kind has its test hooks (a cell without them fails
    ``test_every_cell_kind_has_test_hooks`` alone)."""
    return [w for w in workloads() if os.path.isfile(hooks_path(kind_of(w)))]


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

        def load_tiny(spec, workload, root=harness.ROOT):
            cell, config, traffic = load_cell(spec, workload, root)
            shrink(config, traffic, root)
            return cell, config, traffic

        monkeypatch.setattr(harness, "load_cell", load_tiny)


def shrink(config: dict, traffic: dict, root: str = ROOT) -> None:
    """Cut a cell's files in place to a CPU size, by its kind's hooks."""
    hooks(traffic["kind"], root).tiny(config, traffic)


def run_tiny(workload: str, capsys, seconds: float = 0.5, trace: bool = False,
             seed: int = 2**31 + 3, root: str = ROOT) -> tuple:
    """One run under ``steer``; returns ``(result, stdout_lines, stderr_lines)``."""
    result = harness.run(workload, seed, seconds, trace, t_process=time.perf_counter(), root=root)
    out, err = capsys.readouterr()
    return result, out.strip().splitlines(), err.strip().splitlines()


def check_twice(monkeypatch) -> list:
    """Make every mix built check its sample twice: as the run does, in its
    worker pool where it has one, and then in this process on the same
    sample.  Returns ``[(pooled, run's numbers, in-process numbers)]``."""
    import mixes

    pairs = []
    build = mixes.build

    def build_twice(*a, **k):
        mix = build(*a, **k)
        check = mix.check

        def check_both(done, rng, pool, control=None):
            twin = np.random.Generator(type(rng.bit_generator)())
            twin.bit_generator.state = rng.bit_generator.state
            got = check(done, rng, pool, control)
            pairs.append((pool is not None, got, check(done, twin, None, control)))
            return got

        mix.check = check_both
        return mix

    monkeypatch.setattr(mixes, "build", build_twice)
    return pairs


# ---- faults planted where ``scenario.sweep`` makes its per-cell results

SUMMARY_FIELDS = ("n_cold", "n_warm", "n_reject", "time_running", "time_idle",
                  "sum_cold_resp", "sum_warm_resp")


def _unchanged(cells):
    return [dataclasses.replace(s, **{f: np.zeros_like(np.asarray(getattr(s, f), np.float64))
                                      for f in SUMMARY_FIELDS}) for s in cells]


def _half(cells):
    def halve(a):
        a = np.array(a, copy=True)
        h = len(a) // 2
        a[h:] = a[: len(a) - h]
        return a

    return [dataclasses.replace(s, **{f: halve(getattr(s, f)) for f in SUMMARY_FIELDS})
            for s in cells]


def _altered(cells):
    return cells[1:] + cells[:1]


def plant_in_sweep(monkeypatch, fault: str) -> None:
    """Plant ``fault`` in the per-cell ``SimulationSummary`` lists of
    ``scenario.sweep``, on every backend: the block backends'
    ``_block_cells`` and the scan's ``_scan_dispatch`` collector."""
    from repro.core import scenario

    bend = {"unchanged": _unchanged, "half": _half, "altered": _altered}[fault]
    block_cells = scenario._block_cells
    scan_dispatch = scenario._scan_dispatch

    def broken_block(*a, **k):
        cells, win = block_cells(*a, **k)
        return bend(list(cells)), win

    def broken_dispatch(*a, **k):
        collect = scan_dispatch(*a, **k)

        def broken_collect():
            cells, win = collect()
            return bend(list(cells)), win

        return broken_collect

    monkeypatch.setattr(scenario, "_block_cells", broken_block)
    monkeypatch.setattr(scenario, "_scan_dispatch", broken_dispatch)
