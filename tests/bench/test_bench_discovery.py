"""A configuration, a traffic mix and a per-layer metric added under new
names are found by name: BENCHMARK.json gains entries and no existing
file of the benchmark changes."""

import json
import os
import shutil

from bench_helpers import ROOT, run_tiny, shrink, steer

NEW_METRIC = '''"""Requests in the traced window (a test's reader)."""


def read(ctx):
    return float(len(ctx.requests))
'''


def test_new_files_are_found_by_name(tmp_path, monkeypatch, capsys):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (root / "bench" / p).read_bytes()
              for p in ("harness.py", "mixes.py", "traces.py", "run.py")}
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())

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
    spec["workloads"].append({"name": "busier-grid", "config": "lambda-busier",
                              "traffic": "tiny-grid", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "lambda-sweep-pallas" in m["workloads"]:
            m["workloads"].append("busier-grid")
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
    for p, data in before.items():
        assert (root / "bench" / p).read_bytes() == data
