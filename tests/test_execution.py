"""Execution plans + engine/backend registry + device-sharded sweeps.

Single-device behaviour (plan↔kwarg equivalence, capability errors,
GridResult.sel/to_dict, shard_map on a 1-device mesh) runs in-process;
the real multi-device bitwise-equality acceptance runs in a subprocess
with ``--xla_force_host_platform_device_count=4`` (JAX pins the device
count at first init).
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (
    Execution,
    ExpSimProcess,
    Scenario,
    registered_backends,
    registered_engines,
)
from repro.core import execution as exe_mod
from repro.core import scenario as scn_mod
from repro.core import simulator as sim_mod

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def base_scn(**kw):
    d = dict(
        arrival_process=ExpSimProcess(rate=0.8),
        warm_service_process=ExpSimProcess(rate=0.5),
        cold_service_process=ExpSimProcess(rate=0.4),
        expiration_threshold=20.0,
        sim_time=400.0,
        skip_time=10.0,
        slots=32,
    )
    d.update(kw)
    return Scenario(**d)


OVER = {"expiration_threshold": [10.0, 30.0], "arrival_rate": [0.5, 1.0]}
STEPS = 800


class TestExecutionPlan:
    def test_defaults(self):
        e = Execution()
        assert (e.engine, e.backend, e.shard, e.donate) == (
            "scan", "scan", None, True,
        )

    def test_validation(self):
        with pytest.raises(ValueError, match="shard"):
            Execution(shard="replicas")
        with pytest.raises(ValueError, match="precision"):
            Execution(precision="f16")
        with pytest.raises(ValueError, match="block_k"):
            Execution(block_k=0)
        with pytest.raises(ValueError, match="devices"):
            Execution(devices=0)

    def test_devices_sequence_normalized(self):
        e = Execution(devices=jax.devices())
        assert isinstance(e.devices, tuple)
        assert e.resolved_devices() == tuple(jax.devices())
        assert Execution(devices=1).n_devices == 1
        with pytest.raises(ValueError, match="devices"):
            Execution(devices=len(jax.devices()) + 1).resolved_devices()

    def test_mesh_is_1d_grid_axis(self):
        m = Execution(devices=1).mesh()
        assert m.axis_names == ("grid",)
        assert int(m.devices.size) == 1


class TestRegistry:
    def test_registered_engines_and_capabilities(self):
        engines = registered_engines()
        assert {"scan", "temporal", "par"} <= set(engines)
        assert engines["scan"].sweepable
        assert engines["temporal"].backends == ("scan", "pallas", "ref")
        assert engines["par"].backends == ("scan", "pallas", "ref")
        assert not engines["temporal"].sweepable
        assert engines["scan"].windowed_backends == ("scan", "pallas", "ref")

    def test_registered_backends_and_capabilities(self):
        backends = registered_backends()
        assert {"scan", "pallas", "ref"} <= set(backends)
        assert backends["scan"].precision == "f64"
        assert backends["scan"].shardable
        assert backends["ref"].precision == "f32"
        # the f32 block backends are full citizens of the sharded matrix
        assert backends["pallas"].shardable
        assert backends["ref"].shardable
        # per-engine launchers: the pool-state engines share one, par has
        # its own finish[M, c] kernel launcher
        for name in ("pallas", "ref"):
            spec = backends[name]
            assert spec.launch_for("scan") is spec.launch_for("temporal")
            assert spec.launch_for("par") is not spec.launch
        with pytest.raises(ValueError, match="no row launcher"):
            backends["ref"].launch_for("nope")

    def test_unknown_names_list_registered(self):
        with pytest.raises(ValueError, match=r"unknown engine 'nope'.*par.*scan.*temporal"):
            Execution(engine="nope").resolve()
        with pytest.raises(ValueError, match=r"unknown backend 'nope'.*pallas.*ref.*scan"):
            Execution(backend="nope").resolve()

    def test_capability_errors(self):
        """Engine × backend validation still fires for combinations an
        engine does not declare (temporal/par now declare the block
        backends, so a scan-only test engine stands in)."""
        from repro.core.execution import register_engine

        @register_engine("scan-only-test", backends=("scan",))
        def scan_only_run(scn, key, plan, **kw):  # pragma: no cover
            return None, None

        try:
            with pytest.raises(
                ValueError, match=r"'scan-only-test' supports backends \('scan',\)"
            ):
                Execution(engine="scan-only-test", backend="ref").resolve()
        finally:
            del exe_mod._ENGINES["scan-only-test"]
        # the former scan-only pairs resolve now
        Execution(engine="temporal", backend="ref").resolve()
        Execution(engine="par", backend="pallas").resolve()

    def test_precision_declaration_checked(self):
        with pytest.raises(ValueError, match="computes in f64"):
            Execution(precision="f32").resolve()
        Execution(precision="f64").resolve()  # matches the scan backend
        Execution(backend="ref", precision="f32").resolve()

    def test_shard_capability_declared(self):
        """Every shipped backend is shardable now; the declaration is
        still enforced for backends that opt out."""
        from repro.core.execution import register_backend, register_engine

        register_backend("noshard-test", precision="f32")

        @register_engine("anyback-test", backends=("scan", "noshard-test"))
        def anyback_run(scn, key, plan, **kw):  # pragma: no cover
            return None, None

        try:
            with pytest.raises(ValueError, match="shardable backends"):
                Execution(
                    engine="anyback-test", backend="noshard-test", shard="grid"
                ).resolve()
        finally:
            del exe_mod._BACKENDS["noshard-test"]
            del exe_mod._ENGINES["anyback-test"]
        Execution(backend="ref", shard="grid").resolve()
        Execution(backend="pallas", shard="grid").resolve()

    def test_sharded_f64_on_block_backend_points_at_scan(self):
        """shard='grid' + precision='f64' on an f32 block backend must
        say where sharded f64 sweeps actually live, not just complain
        about the precision mismatch."""
        for be in ("pallas", "ref"):
            with pytest.raises(ValueError, match="backend='scan'"):
                Execution(backend=be, shard="grid", precision="f64").resolve()
        # plain mismatch (no shard) keeps the generic message
        with pytest.raises(ValueError, match="computes in f32"):
            Execution(backend="ref", precision="f64").resolve()

    def test_block_k_auto_resolution(self):
        """block_k=None derives the chunk from the stream length and the
        VMEM budget, in the fewest balanced chunks; explicit values are
        honoured (clamped to K); either is rounded up to whole 8-event
        sublane tiles.  The lane-major kernels (fleet, par) round to
        whole 128-lane tiles instead."""
        from repro.core.execution import (
            _AUTO_BLOCK_K_MAX,
            _AUTO_SUBLANE_BLOCK_K_MAX,
        )

        e = Execution()
        assert e.block_k is None
        # short stream: one chunk, rounded up to whole sublane tiles
        assert e.resolved_block_k(300) == 304
        # 800 events over the 680-event ceiling: two balanced chunks
        assert _AUTO_SUBLANE_BLOCK_K_MAX == 680
        assert e.resolved_block_k(800) == 400
        assert e.resolved_block_k(10**6) <= _AUTO_SUBLANE_BLOCK_K_MAX
        assert e.resolved_block_k(10**6) % 8 == 0
        assert Execution(block_k=256).resolved_block_k(800) == 256
        assert Execution(block_k=100).resolved_block_k(800) == 104
        assert Execution(block_k=4096).resolved_block_k(800) == 800
        # the lane-major kernels keep whole 128-lane chunks
        assert e.resolved_lane_block_k(800) == 896
        assert e.resolved_lane_block_k(10**6) == _AUTO_BLOCK_K_MAX
        assert _AUTO_BLOCK_K_MAX % 128 == 0
        assert Execution(block_k=100).resolved_lane_block_k(800) == 128

    def test_readme_capability_matrix_matches_registry(self):
        """The README capability matrix is generated from the registry;
        the committed copy must not drift from the declarations."""
        from repro.core.execution import capability_markdown

        readme = open(
            os.path.join(os.path.dirname(__file__), "..", "README.md")
        ).read()
        table = capability_markdown()
        assert table in readme, (
            "README capability matrix is stale; regenerate with "
            "capability_markdown() and paste it in"
        )

    def test_sweep_exposes_resolved_block_k(self):
        g = scn_mod.sweep(
            base_scn(), over=OVER, key=jax.random.key(0), replicas=1,
            steps=STEPS, backend="ref",
        )
        # STEPS=800 is two balanced chunks of whole sublane tiles
        assert g.execution.block_k == 400

    def test_devices_without_shard_rejected(self):
        """devices= only takes effect through shard='grid'; a plan that
        would silently run single-device must fail loudly instead."""
        with pytest.raises(ValueError, match="shard='grid'"):
            Execution(devices=1).resolve()
        Execution(devices=1, shard="grid").resolve()

    def test_third_party_sweepable_engine_rejected_by_sweep(self):
        """sweep()'s grid machinery belongs to the built-in scan engine;
        a foreign engine declaring sweepable must not silently get scan
        semantics run under its name."""
        from repro.core.execution import register_engine

        @register_engine("mine-test", backends=("scan",), sweepable=True)
        def mine_run(scn, key, plan, **kw):  # pragma: no cover - never run
            return None, None

        try:
            with pytest.raises(ValueError, match="built-in 'scan' grid"):
                scn_mod.sweep(
                    base_scn(), over=OVER, key=jax.random.key(0),
                    execution=Execution(engine="mine-test"),
                )
        finally:
            del exe_mod._ENGINES["mine-test"]

    def test_custom_registration_round_trips(self):
        from repro.core.execution import register_engine, resolve_engine

        @register_engine("null-test", backends=("scan",), description="test")
        def null_run(scn, key, plan, **kw):  # pragma: no cover - never run
            return None, None

        try:
            spec = resolve_engine("null-test")
            assert spec.run is null_run
            with pytest.raises(ValueError, match="null-test"):
                Execution(engine="null-test", backend="ref").resolve()
        finally:
            del exe_mod._ENGINES["null-test"]


class TestPlanExecution:
    def test_run_plan_equals_kwargs(self):
        s = base_scn()
        a = scn_mod.run(s, jax.random.key(0), replicas=2)
        b = scn_mod.run(s, jax.random.key(0), replicas=2, execution=Execution())
        np.testing.assert_array_equal(a.summary.n_cold, b.summary.n_cold)
        c = scn_mod.run(s, jax.random.key(0), replicas=2, backend="ref", steps=STEPS)
        d = scn_mod.run(
            s, jax.random.key(0), replicas=2, steps=STEPS,
            execution=Execution(backend="ref"),
        )
        np.testing.assert_array_equal(
            np.asarray(c.summary.n_cold), np.asarray(d.summary.n_cold)
        )

    def test_kwargs_override_plan(self):
        s = base_scn(concurrency_value=2)
        res = scn_mod.run(
            s, jax.random.key(0), replicas=1,
            execution=Execution(engine="scan"), engine="par",
        )
        assert res.summary.time_in_flight is not None  # par summary type

    def test_run_rejects_shard(self):
        with pytest.raises(ValueError, match="sweep"):
            scn_mod.run(
                base_scn(), jax.random.key(0),
                execution=Execution(shard="grid"),
            )

    def test_sweep_rejects_unsweepable_engine(self):
        with pytest.raises(ValueError, match="does not support sweep"):
            scn_mod.sweep(
                base_scn(), over=OVER, key=jax.random.key(0),
                execution=Execution(engine="temporal"),
            )

    def test_sweep_plan_equals_kwargs_bitwise(self):
        s = base_scn()
        kw = dict(over=OVER, key=jax.random.key(3), replicas=2, steps=STEPS)
        a = scn_mod.sweep(s, **kw)
        b = scn_mod.sweep(s, execution=Execution(), **kw)
        np.testing.assert_array_equal(a.cold_start_prob, b.cold_start_prob)
        np.testing.assert_array_equal(a.developer_cost, b.developer_cost)
        # the returned plan carries resolved values (draws, like block_k)
        assert b.execution == Execution(draws="staged")

    def test_sweep_donate_off_matches(self):
        s = base_scn()
        kw = dict(over=OVER, key=jax.random.key(3), replicas=1, steps=STEPS)
        a = scn_mod.sweep(s, **kw)
        b = scn_mod.sweep(s, execution=Execution(donate=False), **kw)
        np.testing.assert_array_equal(a.cold_start_prob, b.cold_start_prob)

    def test_sharded_one_device_mesh_bitwise(self):
        """shard_map over a 1-device 'grid' mesh must already be bitwise
        equal (the multi-device acceptance runs in the subprocess test)."""
        s = base_scn()
        kw = dict(over=OVER, key=jax.random.key(3), replicas=2, steps=STEPS)
        a = scn_mod.sweep(s, **kw)
        b = scn_mod.sweep(s, execution=Execution(shard="grid"), **kw)
        # the sharded executable genuinely ran (count stays flat only on
        # an lru_cache hit of an earlier sharded call, never at zero)
        assert sim_mod.TRACE_COUNTS["simulate_sweep_sharded"] > 0
        np.testing.assert_array_equal(a.cold_start_prob, b.cold_start_prob)
        np.testing.assert_array_equal(a.avg_server_count, b.avg_server_count)
        np.testing.assert_array_equal(a.avg_response_time, b.avg_response_time)


class TestGridResultHelpers:
    def _grid(self):
        return scn_mod.sweep(
            base_scn(),
            over={
                "expiration_threshold": [10.0, 30.0, 60.0],
                "arrival_rate": [0.5, 1.0],
            },
            key=jax.random.key(9),
            replicas=1,
            steps=STEPS,
        )

    def test_sel_drops_named_axis(self):
        g = self._grid()
        s = g.sel(arrival_rate=1.0)
        assert list(s.axes) == ["expiration_threshold"]
        assert s.shape == (3,)
        np.testing.assert_array_equal(s.cold_start_prob, g.cold_start_prob[:, 1])
        np.testing.assert_array_equal(s.provider_cost, g.provider_cost[:, 1])
        assert s.summaries[0] is g.summaries[0, 1]
        # full selection → scalars + the bare summary
        full = g.sel(arrival_rate=0.5, expiration_threshold=30.0)
        assert full.axes == {}
        assert float(full.cold_start_prob) == g.cold_start_prob[1, 0]
        assert full.summaries is g.summaries[1, 0]

    def test_sel_errors_name_values(self):
        g = self._grid()
        with pytest.raises(KeyError, match="unknown axis"):
            g.sel(slots=1)
        with pytest.raises(KeyError, match="not on axis"):
            g.sel(arrival_rate=9.9)

    def test_sel_keeps_windowed_trailing_axis(self):
        s = base_scn(
            skip_time=0.0,
            window_bounds=tuple(np.linspace(0.0, 400.0, 5)),
        )
        g = scn_mod.sweep(
            s,
            over={"expiration_threshold": [10.0, 30.0]},
            key=jax.random.key(2),
            replicas=1,
            steps=STEPS,
        )
        w = g.sel(expiration_threshold=30.0)
        assert w.windowed_cold_prob.shape == (4,)
        np.testing.assert_array_equal(
            w.windowed_cold_prob, g.windowed_cold_prob[1]
        )

    def test_to_dict_json_round_trip(self):
        import json

        g = self._grid()
        d = json.loads(json.dumps(g.to_dict()))
        assert d["axes"]["arrival_rate"] == [0.5, 1.0]
        np.testing.assert_allclose(
            np.asarray(d["cold_start_prob"]), g.cold_start_prob
        )
        assert d["backend"] == "scan"


def test_sharded_sweep_matches_single_device_on_4_devices():
    """The acceptance bar: a 3-axis product grid under a 4-fake-device
    Execution(shard='grid') compiles ONCE and is bitwise-equal cell-by-cell
    to the single-device sweep — including a grid whose flattened row count
    is NOT divisible by the device count (padded tail)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = """
    import jax, numpy as np
    from repro.core import Execution, ExpSimProcess, Scenario, scenario
    from repro.core import simulator as sim_mod

    assert len(jax.devices()) == 4
    scn = Scenario(
        arrival_process=ExpSimProcess(rate=0.8),
        warm_service_process=ExpSimProcess(rate=0.5),
        cold_service_process=ExpSimProcess(rate=0.4),
        expiration_threshold=20.0, sim_time=400.0, skip_time=10.0, slots=32,
    )
    # 3-axis grid: C = 3 thresholds * 2 rates * 2 horizons * 1 replica = 12
    over = {
        "expiration_threshold": [10.0, 30.0, 60.0],
        "arrival_rate": [0.5, 1.0],
        "sim_time": [300.0, 400.0],
    }
    kw = dict(key=jax.random.key(5), replicas=1, steps=800)
    single = scenario.sweep(scn, over=over, **kw)
    before = sim_mod.TRACE_COUNTS["simulate_sweep_sharded"]
    plan = Execution(devices=4, shard="grid")
    shard = scenario.sweep(scn, over=over, execution=plan, **kw)
    assert sim_mod.TRACE_COUNTS["simulate_sweep_sharded"] == before + 1, "one compile"
    for f in ("cold_start_prob", "avg_server_count", "avg_response_time",
              "developer_cost", "provider_cost"):
        np.testing.assert_array_equal(getattr(shard, f), getattr(single, f))
    # same structure, new values: pure cache hit
    scenario.sweep(scn, over={
        "expiration_threshold": [15.0, 25.0, 45.0],
        "arrival_rate": [0.6, 1.1],
        "sim_time": [250.0, 350.0],
    }, execution=plan, **kw)
    assert sim_mod.TRACE_COUNTS["simulate_sweep_sharded"] == before + 1

    # padded tail: C = 3 * 2 = 6 rows on 4 devices (pad 2)
    over2 = {"expiration_threshold": [10.0, 30.0, 60.0], "sim_time": [300.0, 400.0]}
    s1 = scenario.sweep(scn, over=over2, **kw)
    s2 = scenario.sweep(scn, over=over2, execution=Execution(shard="grid"), **kw)
    np.testing.assert_array_equal(s2.cold_start_prob, s1.cold_start_prob)
    np.testing.assert_array_equal(s2.avg_server_count, s1.avg_server_count)
    print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout


def test_sharded_block_sweep_matches_single_device_on_4_devices():
    """The block-backend acceptance bar: an f32 ref/pallas sweep under a
    4-fake-device Execution(shard='grid') compiles ONCE and is
    bitwise-equal cell-by-cell to the single-device sweep — including a
    padded tail (C=6 rows on 4 devices → lcm(BLOCK_R, 4)=8) and the
    in-kernel windowed grids on an *irregular* window grid."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = """
    import jax, numpy as np
    from repro.core import Execution, ExpSimProcess, Scenario, scenario
    from repro.core import scenario as scn_mod

    assert len(jax.devices()) == 4
    scn = Scenario(
        arrival_process=ExpSimProcess(rate=0.8),
        warm_service_process=ExpSimProcess(rate=0.5),
        cold_service_process=ExpSimProcess(rate=0.4),
        expiration_threshold=20.0, sim_time=400.0, skip_time=0.0, slots=32,
        window_bounds=(0.0, 60.0, 150.0, 400.0),  # irregular widths
    )
    # C = 3 thresholds * 2 horizons * 1 replica = 6 rows: padded tail
    over = {"expiration_threshold": [10.0, 30.0, 60.0], "sim_time": [300.0, 400.0]}
    kw = dict(key=jax.random.key(5), replicas=1, steps=800)
    fields = ("cold_start_prob", "avg_server_count", "avg_response_time",
              "windowed_cold_prob", "windowed_arrivals",
              "windowed_instance_count")
    for be in ("ref", "pallas"):
        single = scenario.sweep(scn, over=over, backend=be, **kw)
        before = scn_mod.TRACE_COUNTS["sweep_block_sharded"]
        plan = Execution(backend=be, devices=4, shard="grid")
        shard = scenario.sweep(scn, over=over, execution=plan, **kw)
        assert scn_mod.TRACE_COUNTS["sweep_block_sharded"] == before + 1, "one trace"
        for f in fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(shard, f)), np.asarray(getattr(single, f)),
                err_msg=f"{be}:{f}",
            )
        # same structure, new values: pure cache hit
        scenario.sweep(scn, over={
            "expiration_threshold": [15.0, 25.0, 45.0],
            "sim_time": [250.0, 350.0],
        }, execution=plan, **kw)
        assert scn_mod.TRACE_COUNTS["sweep_block_sharded"] == before + 1
    print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout
