"""The chip smoke's phases at tiny sizes on the CPU (Pallas interpreted):
each phase's own checks — zero warm traces, finite cells, pallas bitwise
against its ref mirror, online trace deltas 1 then 0, the Table 1 bounds,
the pyref oracle — must hold here before they are asked of the chip.  Only
the device gate is skipped; the four-device phase runs on 4 fake CPU
devices in a fresh process."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_device_gate_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_gate()


def test_main_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok": true' not in capsys.readouterr().out


def test_phase_table1():
    out = chip_smoke.phase_table1(sim_time=3e4, replicas=1)
    assert out["wall_s"] > 0


def test_phase_sweep():
    grids = chip_smoke.phase_sweep(
        n_thr=2, n_rate=2, replicas=2, sim_time=300.0
    )
    assert set(grids) == {"scan", "pallas_staged", "pallas_fused"}
    for g in grids.values():
        assert g.cold_start_prob.shape == (2, 2)


def test_phase_fleet():
    grids = chip_smoke.phase_fleet(n_thr=2, replicas=1, sim_time=300.0)
    assert set(grids) == {"scan", "pallas"}


def test_phase_online():
    recs = chip_smoke.phase_online(n_ticks=3, replicas=2, n_bins=4, bin_width=30.0)
    assert [len(r) for r in recs.values()] == [3, 3]


def test_phase_pyref():
    out = chip_smoke.phase_pyref(sim_time=500.0, replicas=2)
    assert out["integral_max_rel"] <= 1e-9


def test_phase_four_chips_on_fake_devices():
    """The grid-sharded phase over 4 fake CPU devices (JAX fixes the device
    count at first init, hence the fresh process)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = """
    import json, sys
    sys.path.insert(0, %r)
    import chip_smoke
    out = chip_smoke.phase_four_chips(n_thr=2, n_rate=2, replicas=2,
                                      sim_time=300.0)
    print(json.dumps(sorted(out)))
    """ % ROOT
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == ["pallas", "scan"]
    assert any(line.startswith("[4chip/pallas]") for line in lines)
