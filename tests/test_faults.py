"""Platform fault injection (DESIGN.md §15).

Covers the fault layer end to end:

* model validation — :class:`CapacityProfile` / :class:`FaultModel`
  invariants and the pointed scenario-level capability errors,
* the bitwise no-op guarantee — ``faults=FaultModel()`` (all defaults)
  reproduces a faultless run exactly on every backend, single-function
  and fleet,
* backend agreement under *active* faults — scan/pallas/ref produce
  identical decision counts, pallas == ref bitwise,
* the ``simulate_pyref`` / ``simulate_fleet_pyref`` oracle staying
  decision-exact with crashes + capacity churn on,
* mass conservation — arrivals land in exactly one outcome bucket, and
  a capacity step evicts exactly the warm-pool surplus,
* sweep integration — crash-rate × threshold grids compile once,
  availability lands in the grid, fault axes on a non-fault engine
  raise a pointed error naming ``EngineSpec.faults_backends``,
* ``reliability_report`` carrying the fault block, and
* the online chaos path — a service whose base scenario carries a
  capacity dip holds its last good recommendation (``degraded=True``)
  through a stalled tick, with zero recompiles.
"""

import jax
import numpy as np
import pytest

from repro.core import simulator as sim_mod
from repro.core.faults import CapacityProfile, FaultModel
from repro.core.fleet import FleetFunction, FleetScenario, fleet_run
from repro.core.metrics import reliability_report
from repro.core.processes import (
    DeterministicSimProcess,
    ExpSimProcess,
    TraceArrivalProcess,
)
from repro.core.pyref import simulate_fleet_pyref, simulate_pyref
from repro.core.scenario import Scenario, run, sweep
from repro.core.simulator import draw_crash_uniforms, draw_workload_samples
from repro.kernels import faas_event_step as fes

BACKENDS = ("scan", "pallas", "ref")

COUNTS = ("n_cold", "n_warm", "n_reject")
FAULT_COUNTS = ("n_crash", "n_evict", "n_interrupt")
FLOATS = ("time_running", "time_idle", "sum_cold_resp", "sum_warm_resp")


def base_scn(**kw):
    d = dict(
        arrival_process=ExpSimProcess(rate=0.9),
        warm_service_process=ExpSimProcess(rate=0.5),
        cold_service_process=ExpSimProcess(rate=1.0 / 3.0),
        expiration_threshold=40.0,
        max_concurrency=25,
        sim_time=400.0,
        skip_time=20.0,
        slots=64,
    )
    d.update(kw)
    return Scenario(**d)


ACTIVE = FaultModel(
    crash_rate=0.01,
    capacity=CapacityProfile(edges=(150.0, 280.0), values=(30.0, 5.0, 30.0)),
)


def _mk_fn(name, rate, warm, cold, t_exp, limit):
    return FleetFunction(
        name=name,
        arrival_process=ExpSimProcess(rate=rate),
        warm_service_process=ExpSimProcess(rate=1.0 / warm),
        cold_service_process=ExpSimProcess(rate=1.0 / cold),
        expiration_threshold=t_exp,
        max_concurrency=limit,
    )


def base_fleet(**kw):
    d = dict(
        functions=(
            _mk_fn("a", 0.5, 1.5, 3.0, 40.0, 20),
            _mk_fn("b", 0.8, 2.0, 4.0, 60.0, 25),
            _mk_fn("c", 0.3, 1.0, 2.5, 30.0, 15),
        ),
        n_cluster=40,
        sim_time=400.0,
        skip_time=20.0,
    )
    d.update(kw)
    return FleetScenario(**d)


FLEET_ACTIVE = FaultModel(
    crash_rate=0.01,
    capacity=CapacityProfile(edges=(150.0, 280.0), values=(40.0, 10.0, 40.0)),
)


# ---------------------------------------------------------------------------
# model validation
# ---------------------------------------------------------------------------


class TestFaultModelValidation:
    def test_capacity_profile_shape(self):
        with pytest.raises(ValueError, match="len\\(values\\)"):
            CapacityProfile(edges=(10.0,), values=(5.0,))
        with pytest.raises(ValueError, match="strictly increasing"):
            CapacityProfile(edges=(20.0, 10.0), values=(5.0, 5.0, 5.0))
        with pytest.raises(ValueError, match="finite and >= 0"):
            CapacityProfile(edges=(10.0,), values=(5.0, -1.0))

    def test_capacity_profile_lookup(self):
        p = CapacityProfile(edges=(10.0, 20.0), values=(8.0, 2.0, 6.0))
        assert p.value(0.0) == 8.0
        assert p.value(10.0) == 2.0  # right-closed step
        assert p.value(25.0) == 6.0
        assert p.floor == 2.0

    def test_fault_model_validation(self):
        with pytest.raises(ValueError, match="crash_rate"):
            FaultModel(crash_rate=-0.1)
        with pytest.raises(TypeError, match="CapacityProfile"):
            FaultModel(capacity=(10.0,))
        assert not FaultModel().enabled
        assert FaultModel(crash_rate=1e-3).enabled
        assert ACTIVE.cap_steps == 3 and ACTIVE.crashes

    def test_scenario_capability_errors(self):
        with pytest.raises(ValueError, match="FaultModel"):
            base_scn(faults="crashy")
        with pytest.raises(ValueError, match="windowed"):
            base_scn(faults=ACTIVE, window_bounds=(0.0, 200.0, 400.0))
        with pytest.raises(ValueError, match="histogram"):
            base_scn(faults=ACTIVE, track_histogram=True)

    def test_fleet_rejects_faults_with_queue(self):
        with pytest.raises(ValueError, match="queue_depth"):
            base_fleet(faults=FLEET_ACTIVE, queue_depth=4)
        # a disabled model is fine next to a queue
        base_fleet(faults=FaultModel(), queue_depth=4)


# ---------------------------------------------------------------------------
# trivial FaultModel() is a bitwise no-op
# ---------------------------------------------------------------------------


class TestTrivialNoOp:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_single_function(self, backend):
        key = jax.random.key(11)
        a = run(base_scn(), key, replicas=2, backend=backend).summary
        b = run(
            base_scn(faults=FaultModel()), key, replicas=2, backend=backend
        ).summary
        for f in COUNTS + FLOATS:
            assert np.array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            ), f
        # counters are absent or identically zero; availability is pristine
        assert b.n_crash is None or not np.asarray(b.n_crash).any()
        assert b.availability == 1.0

    def test_fleet(self):
        key = jax.random.key(12)
        for backend in BACKENDS:
            a = fleet_run(base_fleet(), key, replicas=2, backend=backend)
            b = fleet_run(
                base_fleet(faults=FaultModel()),
                key,
                replicas=2,
                backend=backend,
            )
            for sa, sb in zip(a.summary.summaries, b.summary.summaries):
                for f in COUNTS + FLOATS:
                    assert np.array_equal(
                        np.asarray(getattr(sa, f)), np.asarray(getattr(sb, f))
                    ), (backend, f)


# ---------------------------------------------------------------------------
# backend agreement + pyref oracle under active faults
# ---------------------------------------------------------------------------


class TestBackendAgreement:
    def test_single_function_counts_agree(self):
        key = jax.random.key(5)
        scn = base_scn(faults=ACTIVE)
        outs = {
            b: run(scn, key, replicas=3, backend=b).summary for b in BACKENDS
        }
        for b in ("pallas", "ref"):
            for f in COUNTS + FAULT_COUNTS:
                assert np.array_equal(
                    np.asarray(getattr(outs["scan"], f), np.int64),
                    np.asarray(getattr(outs[b], f), np.int64),
                ), (b, f)
        # the f32 block twins are bitwise equal, not merely count-equal
        for f in COUNTS + FAULT_COUNTS + FLOATS:
            assert np.array_equal(
                np.asarray(getattr(outs["pallas"], f)),
                np.asarray(getattr(outs["ref"], f)),
            ), f

    def test_fleet_counts_agree(self):
        key = jax.random.key(9)
        fleet = base_fleet(faults=FLEET_ACTIVE)
        outs = {
            b: fleet_run(fleet, key, replicas=2, backend=b).summary
            for b in BACKENDS
        }
        for b in ("pallas", "ref"):
            for f_i in range(len(fleet.functions)):
                for f in COUNTS + FAULT_COUNTS:
                    assert np.array_equal(
                        np.asarray(
                            getattr(outs["scan"].summaries[f_i], f), np.int64
                        ),
                        np.asarray(
                            getattr(outs[b].summaries[f_i], f), np.int64
                        ),
                    ), (b, f_i, f)
        for f_i in range(len(fleet.functions)):
            for f in COUNTS + FAULT_COUNTS + FLOATS:
                assert np.array_equal(
                    np.asarray(getattr(outs["pallas"].summaries[f_i], f)),
                    np.asarray(getattr(outs["ref"].summaries[f_i], f)),
                ), (f_i, f)

    def test_pyref_decision_exact_single(self):
        key = jax.random.key(5)
        scn = base_scn(faults=ACTIVE)
        s = run(scn, key, replicas=2, backend="scan").summary
        samples = draw_workload_samples(scn, key, 2, scn.steps_needed())
        dts, warms, colds = [np.asarray(x) for x in samples]
        cu = np.asarray(draw_crash_uniforms(key, 2, dts.shape[1]), np.float32)
        cap = ACTIVE.capacity
        for r in range(2):
            ref = simulate_pyref(
                dts[r], warms[r], colds[r],
                scn.expiration_threshold, scn.max_concurrency,
                scn.sim_time, scn.skip_time,
                crash_rate=ACTIVE.crash_rate, crash_u=cu[r],
                cap_edges=np.asarray(cap.edges, np.float64),
                cap_values=np.asarray(cap.values, np.float64),
            )
            for f in COUNTS + FAULT_COUNTS:
                assert int(np.asarray(getattr(s, f))[r]) == getattr(
                    ref, f
                ), (r, f)

    def test_pyref_decision_exact_fleet(self):
        from repro.core import fleet as fleet_mod

        fleet = base_fleet(faults=FLEET_ACTIVE)
        key = jax.random.key(9)
        fs = fleet_run(fleet, key, replicas=2, backend="scan").summary
        staged = fleet_mod._stage_fleet(fleet, key, 2, None, fleet.sim_time)
        cu = np.asarray(
            draw_crash_uniforms(key, 2, staged["times"].shape[1]), np.float32
        )
        cap = fleet.faults.capacity
        for r in range(2):
            py = simulate_fleet_pyref(
                staged["times"][r], staged["fids"][r],
                staged["warms"][r], staged["colds"][r],
                [f.expiration_threshold for f in fleet.functions],
                [f.max_concurrency for f in fleet.functions],
                fleet.n_cluster, fleet.queue_depth,
                fleet.sim_time, fleet.skip_time, prestamped=True,
                crash_rate=fleet.faults.crash_rate, crash_u=cu[r],
                cap_edges=np.asarray(cap.edges, np.float64),
                cap_values=np.asarray(cap.values, np.float64),
            )
            for f_i in range(len(fleet.functions)):
                for f in COUNTS + FAULT_COUNTS:
                    assert int(
                        np.asarray(getattr(fs.summaries[f_i], f))[r]
                    ) == int(np.asarray(getattr(py, f))[f_i]), (r, f_i, f)


# ---------------------------------------------------------------------------
# conservation properties
# ---------------------------------------------------------------------------


class TestConservation:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_mass_conservation_single(self, backend):
        """Every arrival lands in exactly one bucket: completion,
        crash-interruption, or rejection (reliability off)."""
        s = run(
            base_scn(faults=ACTIVE), jax.random.key(21), replicas=3,
            backend=backend,
        ).summary
        arrivals = np.asarray(s.n_requests, np.int64)
        completions = np.asarray(s.n_completions, np.int64)
        interrupted = np.asarray(s.n_interrupt, np.int64)
        rejected = np.asarray(s.n_reject, np.int64)
        assert (arrivals == completions + interrupted + rejected).all()
        assert 0.0 <= s.availability <= 1.0

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_mass_conservation_with_reliability(self, backend):
        """With the reliability layer on too, the buckets refine to
        completions + timeouts + failures + interruptions + rejections."""
        from repro.core.reliability import FailurePolicy, Reliability

        rel = Reliability(failure=FailurePolicy(p_fail=0.1, t_timeout=6.0))
        s = run(
            base_scn(faults=ACTIVE, reliability=rel),
            jax.random.key(23), replicas=3, backend=backend,
        ).summary
        arrivals = np.asarray(s.n_requests, np.int64)
        total = (
            np.asarray(s.n_completions, np.int64)
            + np.asarray(s.n_timeout, np.int64)
            + np.asarray(s.n_fail, np.int64)
            + np.asarray(s.n_interrupt, np.int64)
            + np.asarray(s.n_reject, np.int64)
        )
        assert (arrivals == total).all()

    def test_mass_conservation_fleet(self):
        fs = fleet_run(
            base_fleet(faults=FLEET_ACTIVE), jax.random.key(25), replicas=2,
            backend="scan",
        ).summary
        for f_i, s in enumerate(fs.summaries):
            arrivals = np.asarray(fs.arrivals[f_i], np.int64)
            total = (
                np.asarray(s.n_cold, np.int64)
                + np.asarray(s.n_warm, np.int64)
                + np.asarray(s.n_reject, np.int64)
            )
            assert (arrivals == total).all(), f_i

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_capacity_step_evicts_warm_pool_delta(self, backend):
        """Deterministic trace: 4 overlapping cold starts build a 4-deep
        warm pool; a capacity step to 1 must evict exactly the surplus 3
        (the warm-pool delta) at the next event, which then starts warm."""
        scn = Scenario(
            arrival_process=TraceArrivalProcess(
                timestamps=(1.0, 1.5, 2.0, 2.5, 60.0)
            ),
            warm_service_process=DeterministicSimProcess(interval=10.0),
            cold_service_process=DeterministicSimProcess(interval=10.0),
            expiration_threshold=200.0,
            max_concurrency=16,
            # the trace tiles past its last stamp to fill the buffer;
            # a 70s horizon keeps the replayed tail (t >= 72) inert
            sim_time=70.0,
            skip_time=0.0,
            slots=16,
            faults=FaultModel(
                capacity=CapacityProfile(edges=(50.0,), values=(30.0, 1.0))
            ),
        )
        s = run(scn, jax.random.key(0), replicas=1, backend=backend).summary
        assert int(np.asarray(s.n_cold)[0]) == 4
        assert int(np.asarray(s.n_evict)[0]) == 3  # 4-deep pool -> cap 1
        assert int(np.asarray(s.n_warm)[0]) == 1  # survivor serves t=60
        assert int(np.asarray(s.n_reject)[0]) == 0
        assert int(np.asarray(s.n_crash)[0]) == 0


# ---------------------------------------------------------------------------
# sweep integration
# ---------------------------------------------------------------------------


class TestFaultSweeps:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_crash_rate_x_threshold_compiles_once(self, backend):
        scn = base_scn(faults=ACTIVE)
        over = {
            "crash_rate": [0.005, 0.02],
            "expiration_threshold": [20.0, 60.0],
        }
        counters = {
            "scan": (sim_mod.TRACE_COUNTS, "simulate_sweep"),
            "pallas": (fes.TRACE_COUNTS, "faas_sweep_pallas"),
            "ref": (
                __import__(
                    "repro.core.scenario", fromlist=["TRACE_COUNTS"]
                ).TRACE_COUNTS,
                "sweep_block_ref",
            ),
        }
        counts, name = counters[backend]
        # start cold: another test may already have compiled these shapes
        jax.clear_caches()
        before = counts[name]
        g = sweep(
            scn, over=over, key=jax.random.key(31), replicas=2,
            backend=backend, steps=400,
        )
        assert counts[name] == before + 1  # 2x2 grid, one trace
        assert g.availability.shape == (2, 2)
        assert np.isfinite(g.availability).all()
        assert (g.availability <= 1.0).all()
        # a higher crash hazard cannot make the platform more available
        # (same threshold column, same draws)
        assert (g.availability[0] >= g.availability[1]).all()

    def test_capacity_profiles_share_one_trace(self):
        scn = base_scn(faults=ACTIVE)
        profs = [
            CapacityProfile(edges=(100.0, 250.0), values=(30.0, 8.0, 30.0)),
            CapacityProfile(edges=(150.0, 300.0), values=(30.0, 4.0, 30.0)),
        ]
        before = sim_mod.TRACE_COUNTS["simulate_sweep"]
        g = sweep(
            scn, over={"capacity": profs}, key=jax.random.key(33),
            replicas=2, backend="scan", steps=400,
        )
        assert sim_mod.TRACE_COUNTS["simulate_sweep"] == before + 1
        assert g.availability.shape == (2,)

    def test_fault_axes_on_non_fault_engine_pointed_error(self):
        scn = base_scn(faults=ACTIVE, max_concurrency=8)
        with pytest.raises(ValueError, match="faults_backends"):
            run(scn, jax.random.key(1), replicas=1, engine="par")

    def test_reliability_report_carries_fault_block(self):
        s = run(
            base_scn(faults=ACTIVE), jax.random.key(41), replicas=2,
            backend="scan",
        ).summary
        rep = reliability_report(s)
        for k in ("crashes", "evictions", "interrupted", "availability"):
            assert k in rep
        assert rep["crashes"] == float(np.asarray(s.n_crash).sum())
        assert rep["availability"] == s.availability
        # faultless, reliability-less runs still get the pointed error
        plain = run(base_scn(), jax.random.key(41), replicas=1).summary
        with pytest.raises(ValueError, match="reliability or fault"):
            reliability_report(plain)


# ---------------------------------------------------------------------------
# online chaos: capacity loss + ingest stall -> held, degraded advice
# ---------------------------------------------------------------------------


class TestOnlineChaos:
    def test_degraded_tick_holds_last_good_recommendation(self):
        from repro.core.scenario import TRACE_COUNTS as SCN_COUNTS
        from repro.serving.online import OnlineConfig, OnlineWhatIfService

        base = Scenario(
            arrival_process=ExpSimProcess(rate=1.0),
            warm_service_process=ExpSimProcess(rate=0.5),
            cold_service_process=ExpSimProcess(rate=1.0 / 3.0),
            expiration_threshold=60.0,
            max_concurrency=20,
            sim_time=100.0,
            skip_time=0.0,
            faults=FaultModel(
                crash_rate=0.01,
                capacity=CapacityProfile(edges=(40.0,), values=(20.0, 4.0)),
            ),
        )
        cfg = OnlineConfig(
            rate_ceiling=4.0, n_bins=4, bin_width=10.0, overlap=False,
            thresholds=(30.0, 120.0), replicas=2,
        )
        svc = OnlineWhatIfService(base, cfg)
        rng = np.random.default_rng(7)
        svc.observe(np.cumsum(rng.exponential(1.0, 60)))
        r0 = svc.tick()  # warmup: compiles, healthy
        assert not r0.degraded
        before = _trace_total()
        r1 = svc.tick()  # capacity-loss tick with stalled ingest
        assert _trace_total() == before, "degraded tick must not recompile"
        assert r1.degraded and "stalled" in r1.degraded_reason
        # held: the advice is r0's, verbatim
        assert r1.threshold == r0.threshold
        assert r1.applied_threshold == r0.applied_threshold
        assert r1.predicted_cold_prob == r0.predicted_cold_prob
        del SCN_COUNTS  # imported for parity with service internals

    def test_checkpoint_restore_resumes_bitwise(self):
        from repro.serving.online import OnlineConfig, OnlineWhatIfService

        base = Scenario(
            arrival_process=ExpSimProcess(rate=1.0),
            warm_service_process=ExpSimProcess(rate=0.5),
            cold_service_process=ExpSimProcess(rate=1.0 / 3.0),
            expiration_threshold=60.0,
            max_concurrency=20,
            sim_time=100.0,
            skip_time=0.0,
        )
        cfg = OnlineConfig(
            rate_ceiling=4.0, n_bins=4, bin_width=10.0, overlap=False,
            thresholds=(30.0, 120.0), replicas=2,
        )
        svc = OnlineWhatIfService(base, cfg)
        rng = np.random.default_rng(3)
        svc.observe(np.cumsum(rng.exponential(1.0, 50)))
        svc.tick()
        snap = svc.checkpoint()
        clone = OnlineWhatIfService(base, cfg)
        clone.restore(snap)
        more = svc.now + np.cumsum(rng.exponential(1.0, 30))
        svc.observe(more)
        clone.observe(more)
        ra, rb = svc.tick(), clone.tick()
        assert ra.threshold == rb.threshold
        assert ra.applied_threshold == rb.applied_threshold
        assert float(ra.rate_mean) == float(rb.rate_mean)
        assert np.array_equal(
            np.asarray(ra.grid.cold_start_prob),
            np.asarray(rb.grid.cold_start_prob),
        )

    def test_restore_rejects_unknown_version(self):
        from repro.serving.online import OnlineConfig, OnlineWhatIfService

        svc = OnlineWhatIfService(
            Scenario(
                arrival_process=ExpSimProcess(rate=1.0),
                warm_service_process=ExpSimProcess(rate=0.5),
                cold_service_process=ExpSimProcess(rate=0.5),
                sim_time=100.0,
                skip_time=0.0,
            ),
            OnlineConfig(rate_ceiling=2.0, n_bins=2, bin_width=10.0),
        )
        with pytest.raises(ValueError, match="version"):
            svc.restore({"version": 99})


def _trace_total() -> int:
    from repro.serving.online import _trace_total as tt

    return tt()
