"""``correct`` catches what it is there to catch.

The control (the plain reference computed one precision below what the
configuration states, in the program's place) fails every cell's limits,
and a run whose timed path is broken underneath comes out not correct,
once for each fault a cell can have: a step that leaves its state
unchanged, half of the replicas left out with the mean taken over the
rest, and an answer altered where it is produced.  Each kind plants them
where its entry point makes its per-cell results (``tests/bench/kinds/``).
"""

import pytest

from bench_helpers import FAULTS, hooked_workloads, hooks, kind_of, run_tiny, steer


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", hooked_workloads())
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch, capsys):
    plant = hooks(kind_of(workload)).plant
    steer(monkeypatch)
    plant(monkeypatch, fault)
    result, out, err = run_tiny(workload, capsys)
    failing = [n for n, c in result["checks"].items() if not c["value"] <= c["limit"]]
    assert result["correct"] is False and failing, result["checks"]


@pytest.mark.parametrize("workload", hooked_workloads())
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
