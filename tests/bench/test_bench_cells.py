"""Every cell of BENCHMARK.json runs end to end on the CPU at a tiny size:
its traffic builds, requests run, the work it counts is what the requests
asked for, the last line is the contract's result, and the reference
gives the same numbers in the run's worker pool as in one process.  Each
cell's kind brings its test hooks (``bench_helpers``)."""

import json

import pytest

from bench_helpers import (ROOT, check_twice, hooks, hooked_workloads, kind_of, run_tiny,
                           shrink, spec, steer, workloads)


@pytest.mark.parametrize("workload", hooked_workloads())
def test_cell_runs_tiny(workload, monkeypatch, capsys):
    steer(monkeypatch)
    result, out, err = run_tiny(workload, capsys)
    last = json.loads(out[-1])
    assert last == result
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] >= 1 and last["failed"] == 0
    s = spec()
    cell = next(w for w in s["workloads"] if w["name"] == workload)
    import harness

    want = {m["name"] for m in s["end_to_end"] if harness.reports(m, cell, s)}
    assert set(last["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    for m in last["metrics"].values():
        assert m["value"] > 0
    # every number compared is printed beside its limit, last on stderr
    assert [ln.split()[1] for ln in err[-len(last["checks"]):]] == list(last["checks"])


@pytest.mark.parametrize("workload", hooked_workloads())
def test_work_count_is_what_requests_ask(workload):
    import mixes
    import harness

    s = spec()
    cell, config, traffic = harness.load_cell(s, workload)
    shrink(config, traffic)
    mix = mixes.build(config, traffic, 7, ROOT)
    want = hooks(traffic["kind"]).asked_work(config, traffic)
    assert mix.work(0) == pytest.approx(want, rel=1e-12)
    assert mix.work(5) == mix.work(0)


@pytest.mark.parametrize("workload", hooked_workloads())
def test_reference_pool_gives_the_same_numbers(workload, monkeypatch, capsys):
    """The chip's path: the reference in spawned workers, which import it by
    name, compares the same numbers as the reference in this process."""
    import harness

    steer(monkeypatch)
    monkeypatch.setattr(harness, "POOL_SIZE", 2)
    pairs = check_twice(monkeypatch)
    result, _, _ = run_tiny(workload, capsys)
    assert result["correct"] is True, result["checks"]
    [(pooled, in_pool, here)] = pairs
    assert pooled and in_pool == here


def test_every_cell_kind_has_test_hooks():
    missing = sorted({kind_of(w) for w in workloads()} - {kind_of(w) for w in hooked_workloads()})
    for kind in missing:
        hooks(kind)  # raises, naming the file to add


def test_hooks_of_an_unknown_kind_name_the_file_to_add():
    with pytest.raises(AssertionError, match="add tests/bench/kinds/no-such-kind.py"):
        hooks("no-such-kind")


def test_an_unknown_kind_names_the_kinds_found():
    import mixes

    found = mixes.kinds(ROOT)
    assert {"online", "sweep"} <= set(found)
    with pytest.raises(ValueError, match="unknown traffic kind 'no-such-kind'") as e:
        mixes.build({}, {"kind": "no-such-kind"}, 7, ROOT)
    assert str(e.value).endswith(f"kinds: {found}")


def test_gate_refuses_a_machine_without_a_tpu():
    import harness

    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.gate(1)


def test_run_exits_nonzero_without_a_tpu(capsys):
    import run

    assert run.main(["--workload", workloads()[0], "--seed", "1", "--seconds", "1"]) != 0
    out, err = capsys.readouterr()
    assert out.strip() == "" and "no TPU" in err


def test_unknown_device_kind_has_no_peaks():
    import harness

    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
