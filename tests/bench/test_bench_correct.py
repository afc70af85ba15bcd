"""``correct`` catches what it is there to catch.

The control (the plain reference computed one precision below what the
configuration states, in the program's place) fails every cell's limits,
and a run whose timed path is broken underneath comes out not correct,
once for each fault a cell can have: a step that leaves its state
unchanged, half of the replicas left out with the mean taken over the
rest, and an answer altered where it is produced.
"""

import dataclasses

import numpy as np
import pytest

from bench_helpers import run_tiny, steer, workloads

FIELDS = ("n_cold", "n_warm", "n_reject", "time_running", "time_idle",
          "sum_cold_resp", "sum_warm_resp")


def _unchanged(cells):
    return [dataclasses.replace(s, **{f: np.zeros_like(np.asarray(getattr(s, f), np.float64))
                                      for f in FIELDS}) for s in cells]


def _half(cells):
    def halve(a):
        a = np.array(a, copy=True)
        h = len(a) // 2
        a[h:] = a[: len(a) - h]
        return a

    return [dataclasses.replace(s, **{f: halve(getattr(s, f)) for f in FIELDS}) for s in cells]


def _altered(cells):
    return cells[1:] + cells[:1]


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered}


def _break(monkeypatch, fault):
    """Plant ``fault`` where each entry point's per-cell results are made."""
    from repro.core import scenario

    bend = FAULTS[fault]
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


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", workloads())
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch, capsys):
    steer(monkeypatch)
    _break(monkeypatch, fault)
    result, out, err = run_tiny(workload, capsys)
    failing = [n for n, c in result["checks"].items() if not c["value"] <= c["limit"]]
    assert result["correct"] is False and failing, result["checks"]


@pytest.mark.parametrize("workload", workloads())
def test_control_fails_and_program_passes(workload, monkeypatch):
    import control
    import harness

    steer(monkeypatch)
    rows = control.readings(workload, [2**31 + 5], 0.5)
    _, _, traffic = harness.load_cell(harness.load_spec(), workload)
    limits = traffic["check"]["limits"]
    for row in rows:
        assert all(row["program"][n] <= lim for n, lim in limits.items()), row
        assert any(row["control"][n] > lim for n, lim in limits.items()), row
