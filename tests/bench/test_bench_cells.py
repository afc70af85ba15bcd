"""Every cell of BENCHMARK.json runs end to end on the CPU at a tiny size:
its traffic builds, requests run, the work it counts is what the requests
asked for, and the last line is the contract's result."""

import json

import pytest

from bench_helpers import shrink, spec, steer, run_tiny, workloads


@pytest.mark.parametrize("workload", workloads())
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


@pytest.mark.parametrize("workload", workloads())
def test_work_count_is_what_requests_ask(workload):
    import mixes
    import harness

    s = spec()
    cell, config, traffic = harness.load_cell(s, workload)
    shrink(config, traffic)
    mix = mixes.build(config, traffic, 7)
    if traffic["kind"] == "sweep":
        rates = mixes.axis_values(traffic["axes"]["arrival_rate"])
        n_thr = len(mixes.axis_values(traffic["axes"]["expiration_threshold"]))
        want = n_thr * traffic["replicas"] * traffic["sim_time"] * sum(rates)
    else:
        want = 0.0
    assert mix.work(0) == pytest.approx(want, rel=1e-12)
    assert mix.work(5) == mix.work(0)


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
