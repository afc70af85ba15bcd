"""New files are found by name, and no file of the benchmark changes.

Added to a copied checkout as new files and ``BENCHMARK.json`` entries:
a configuration, a traffic mix and a per-layer metric of an existing
kind; and a deployment of a new kind, with its kind file, its reference
module, its test hooks, a configuration, a traffic file and a cell.  The
harness finds and runs them, and every file that was already under
``bench/`` and ``tests/bench/`` keeps its bytes."""

import json
import os
import shutil
import sys

from bench_helpers import ROOT, check_twice, hooks, run_tiny, shrink, steer

DIRS = ("bench", os.path.join("tests", "bench"))

NEW_METRIC = '''"""Requests in the traced window (a test's reader)."""


def read(ctx):
    return float(len(ctx.requests))
'''

PROBE_KIND = '''"""A probe kind: the sweep kind's requests, checked through a reference
module of its own."""

import os

import mixes
import reference_probe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Mix(mixes.load_kind("sweep", ROOT).Mix):
    simulate = staticmethod(reference_probe.simulate)
'''

PROBE_REFERENCE = '''"""The probe kind's plain reference, in a module of its own; no JAX."""

import reference

CALLS = [0]  # rows walked in this process


def simulate(*job, rnd=None):
    """One replica row, walked as ``reference.simulate`` walks it."""
    CALLS[0] += 1
    return reference.simulate(*job, rnd=rnd)
'''

PROBE_HOOKS = '''"""Test hooks of the probe kind: the sweep kind's, whose requests it sends."""

import os

from bench_helpers import hooks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
_sweep = hooks("sweep", ROOT)
tiny, asked_work, plant = _sweep.tiny, _sweep.asked_work, _sweep.plant
'''


def _files(root) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for d in DIRS for p in (root / d).rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _checkout(tmp_path):
    """A copy of the benchmark's directories and ``BENCHMARK.json``; returns
    its root, the bytes of every file under the directories, and the spec."""
    root = tmp_path / "checkout"
    for d in DIRS:
        shutil.copytree(os.path.join(ROOT, d), root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    return root, _files(root), spec


def _assert_unchanged(root, before: dict) -> None:
    after = _files(root)
    changed = sorted(str(p) for p, data in before.items() if after.get(p) != data)
    assert not changed, f"files of the benchmark changed: {changed}"


def _add_cell(spec: dict, name: str, config: str, traffic: str) -> None:
    spec["workloads"].append({"name": name, "config": config, "traffic": traffic,
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "lambda-sweep-pallas" in m["workloads"]:
            m["workloads"].append(name)


def test_new_files_are_found_by_name(tmp_path, monkeypatch, capsys):
    root, before, spec = _checkout(tmp_path)

    conf = json.loads((root / "bench/configs/lambda-table1.json").read_text())
    conf["name"] = "lambda-busier"
    conf["arrival"]["rate"] = 1.2
    (root / "bench/configs/lambda-busier.json").write_text(json.dumps(conf))
    traffic = json.loads((root / "bench/traffic/whatif-grid-4096.json").read_text())
    shrink(conf, traffic)
    traffic["axes"]["arrival_rate"] = [0.3, 1.2, 2.0]
    traffic["steps_rate"] = 2.0
    (root / "bench/traffic/tiny-grid.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/requests_traced.py").write_text(NEW_METRIC)

    spec["configs"].append(dict(spec["configs"][0], name="lambda-busier",
                                file="bench/configs/lambda-busier.json"))
    _add_cell(spec, "busier-grid", "lambda-busier", "tiny-grid")
    spec["per_layer"].append({"name": "requests_traced", "unit": "requests", "better": "higher",
                              "source": "device_trace", "layer": "device",
                              "moves": "sim_arrivals_per_s", "workloads": ["busier-grid"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    steer(monkeypatch, tiny=False)
    result, _, _ = run_tiny("busier-grid", capsys, root=str(root))
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"sim_arrivals_per_s", "setup_s"}
    traced, _, _ = run_tiny("busier-grid", capsys, root=str(root), trace=True)
    assert traced["metrics"]["requests_traced"]["value"] == traced["attempted"]
    assert traced["correct"] is True
    _assert_unchanged(root, before)


def test_a_new_kind_is_new_files(tmp_path, monkeypatch, capsys):
    """A probe kind, its reference module (run in spawned workers, as on the
    chip), its test hooks and a cell: the cell runs correct, its control
    fails the limits, a planted fault makes it not correct."""
    import control
    import harness
    import mixes

    monkeypatch.setattr(sys, "path", list(sys.path))  # the copy's bench/ goes on it
    root, before, spec = _checkout(tmp_path)
    (root / "bench/kinds/probe.py").write_text(PROBE_KIND)
    (root / "bench/reference_probe.py").write_text(PROBE_REFERENCE)
    (root / "tests/bench/kinds/probe.py").write_text(PROBE_HOOKS)
    conf = json.loads((root / "bench/configs/lambda-table1.json").read_text())
    conf["name"] = "lambda-probe"
    (root / "bench/configs/lambda-probe.json").write_text(json.dumps(conf))
    traffic = json.loads((root / "bench/traffic/whatif-grid-4096.json").read_text())
    traffic["kind"] = "probe"
    (root / "bench/traffic/probe-grid.json").write_text(json.dumps(traffic))
    spec["configs"].append(dict(spec["configs"][0], name="lambda-probe",
                                file="bench/configs/lambda-probe.json"))
    _add_cell(spec, "probe-grid", "lambda-probe", "probe-grid")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    probe = hooks("probe", str(root))

    steer(monkeypatch)
    _, config, tiny = harness.load_cell(harness.load_spec(str(root)), "probe-grid", str(root))
    assert mixes.build(config, tiny, 7, str(root)).work(0) == probe.asked_work(config, tiny)

    rows = control.readings("probe-grid", [2**31 + 5], 0.5, root=str(root))
    limits = tiny["check"]["limits"]
    for row in rows:
        assert all(row["program"][n] <= lim for n, lim in limits.items()), row
        assert any(row["control"][n] > lim for n, lim in limits.items()), row

    monkeypatch.setattr(harness, "POOL_SIZE", 2)
    pairs = check_twice(monkeypatch)
    result, _, _ = run_tiny("probe-grid", capsys, root=str(root))
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"sim_arrivals_per_s", "setup_s"}
    [(pooled, in_pool, here)] = pairs
    assert pooled and in_pool == here
    reference_probe = sys.modules["reference_probe"]
    assert reference_probe.__file__ == str(root / "bench/reference_probe.py")
    assert reference_probe.CALLS[0] > 0

    monkeypatch.setattr(harness, "POOL_SIZE", 1)
    probe.plant(monkeypatch, "altered")
    broken, _, _ = run_tiny("probe-grid", capsys, root=str(root))
    assert broken["correct"] is False, broken["checks"]
    _assert_unchanged(root, before)
