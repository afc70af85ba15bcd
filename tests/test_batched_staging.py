"""Batched draw staging: a sweep's draw cells in one compiled program.

* **bitwise** — ``simulator.stage_draw_cells`` gives the stacks the
  per-cell ``draw_workload_samples`` loop draws, on a stationary
  (threshold × rate) grid, a step-profile NHPP grid and a gauss/lognorm
  service pair; the sweep's ``repro.sweep.stage`` span says
  ``batched=1`` there and ``batched=0`` on grids it does not serve
  (MMPP arrivals, a retry table);
* **the guard** — under-covered draws still raise, from ``sweep()`` or
  from a deferred sweep's ``result()``;
* **no retrace** — a re-fit online tick and a re-rated sweep of the same
  shape reuse the staging program's compile.
"""

import jax
import numpy as np
import pytest

from repro.core import Scenario
from repro.core import scenario as scenario_mod
from repro.core.drawplan import lower_staged
from repro.core.processes import (
    ExpSimProcess,
    GaussianSimProcess,
    LogNormalSimProcess,
    MMPPArrivalProcess,
    NHPPArrivalProcess,
    PiecewiseConstantRate,
)
from repro.core.reliability import Reliability, RetryPolicy
from repro.core.scenario import TRACE_COUNTS, sweep
from repro.core.simulator import draw_workload_samples, stage_draw_cells
from repro.serving import OnlineConfig, OnlineWhatIfService

PROFILE = PiecewiseConstantRate(edges=(100.0, 250.0), rates=(0.5, 1.5, 0.8))


def base_scn(**kw):
    kw.setdefault("arrival_process", ExpSimProcess(rate=1.0))
    kw.setdefault("warm_service_process", ExpSimProcess(rate=0.5))
    kw.setdefault("cold_service_process", ExpSimProcess(rate=0.4))
    kw.setdefault("sim_time", 400.0)
    kw.setdefault("skip_time", 10.0)
    kw.setdefault("slots", 32)
    return Scenario(**kw)


def stationary_cells():
    base = base_scn()
    return [
        Scenario.of(base, expiration_threshold=t, arrival_rate=r)
        for t in (60.0, 600.0)
        for r in (0.5, 1.0, 1.5)
    ]


def nhpp_cells():
    base = base_scn(arrival_process=NHPPArrivalProcess(profile=PROFILE))
    return [
        Scenario.of(base, expiration_threshold=t) for t in (30.0, 120.0, 600.0)
    ]


def service_pair_cells():
    base = base_scn(
        warm_service_process=GaussianSimProcess(mu=2.0, sigma=0.5),
        cold_service_process=LogNormalSimProcess(mu=0.7, sigma=0.4),
    )
    return [Scenario.of(base, arrival_rate=r) for r in (0.5, 1.5)]


GRIDS = {
    "stationary": (stationary_cells, {
        "expiration_threshold": [60.0, 600.0], "arrival_rate": [0.5, 1.0, 1.5],
    }),
    "nhpp": (nhpp_cells, {"expiration_threshold": [30.0, 120.0, 600.0]}),
    "gauss_lognorm": (service_pair_cells, {"arrival_rate": [0.5, 1.5]}),
}


def per_cell_stacks(cells, key, replicas, n):
    """The per-cell loop the batched program replaces."""
    parts = []
    for c in cells:
        key, sub = jax.random.split(key)
        parts.append(draw_workload_samples(c, sub, replicas, n))
    return tuple(
        np.concatenate([np.asarray(p[j]) for p in parts]) for j in range(3)
    )


def stage(lowered, key, replicas, n):
    kinds, params = lowered
    return stage_draw_cells(key, params, kinds=kinds, replicas=replicas, n=n)


class SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation`` in ``scenario``,
    recording each span's name and metadata, set on entry or later."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **meta):
        self.spans.append((name, meta))
        return _Span(meta)

    def stage(self):
        return [m for n, m in self.spans if n == "repro.sweep.stage"]


class _Span:
    def __init__(self, meta):
        self.meta = meta

    def set_metadata(self, **meta):
        self.meta.update(meta)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def span_log(monkeypatch):
    log = SpanLog()
    monkeypatch.setattr(scenario_mod, "_span", log)
    return log


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_batched_stacks_bitwise_and_stage_span(grid, span_log):
    cells_of, over = GRIDS[grid]
    cells = cells_of()
    key, R, n = jax.random.key(5), 3, 700
    lowered = lower_staged(cells)
    assert lowered is not None
    samples, coverage = stage(lowered, key, R, n)
    want = per_cell_stacks(cells, key, R, n)
    assert len(samples) == 3
    for got, ref in zip(samples, want):
        assert np.asarray(got).dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), ref)
    if cells[0].prestamped:
        # last candidate time; the accepted times sort below it
        cov = np.asarray(coverage)
        real = np.where(want[0] < 1e30, want[0], -np.inf).max(axis=1)
        assert (real <= cov).all()
    else:
        np.testing.assert_allclose(
            np.asarray(coverage), want[0].astype(np.float64).sum(axis=1),
            rtol=1e-12,
        )
    sweep(base_scn() if grid == "stationary" else cells[0], over=over,
          key=key, replicas=R, steps=n)
    assert span_log.stage() == [dict(batched=1, cells=len(cells))]


@pytest.mark.parametrize("grid", ["mmpp", "retry"])
def test_unserved_grids_stage_cell_by_cell(grid, span_log):
    if grid == "mmpp":
        scn = base_scn(arrival_process=MMPPArrivalProcess(
            rate_low=0.5, rate_high=2.0, switch_rate=0.01))
        assert lower_staged([scn]) is None
    else:
        retry = RetryPolicy(max_retries=1)
        scn = base_scn(reliability=Reliability(retry=retry))
    before = TRACE_COUNTS["stage_draw_cells"]
    sweep(scn, over={"expiration_threshold": [60.0, 600.0]},
          key=jax.random.key(2), replicas=2)
    assert span_log.stage() == [dict(batched=0, cells=2)]
    assert TRACE_COUNTS["stage_draw_cells"] == before


def test_sweep_over_profiles_of_other_step_counts_is_bitwise():
    """Profiles of fewer steps pad with edges at +inf: same draws."""
    profiles = [
        PROFILE,
        PiecewiseConstantRate(edges=(50.0,), rates=(1.0, 0.3)),
        PiecewiseConstantRate(edges=(), rates=(0.7,)),
    ]
    base = base_scn(arrival_process=NHPPArrivalProcess(profile=PROFILE))
    cells = [
        Scenario.of(base, arrival_process=NHPPArrivalProcess(profile=p))
        for p in profiles
    ]
    key = jax.random.key(9)
    samples, _ = stage(lower_staged(cells), key, 2, 900)
    for got, ref in zip(samples, per_cell_stacks(cells, key, 2, 900)):
        np.testing.assert_array_equal(np.asarray(got), ref)


# ---------------------------------------------------------------------------
# the coverage guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "ref", "pallas"])
def test_short_nhpp_sweep_raises(backend):
    scn = base_scn(
        arrival_process=NHPPArrivalProcess(profile=PROFILE), sim_time=1000.0
    )
    with pytest.raises(RuntimeError, match="coverage"):
        sweep(scn, over={"expiration_threshold": [60.0, 600.0]},
              key=jax.random.key(0), replicas=2, steps=300, backend=backend)


@pytest.mark.parametrize("arrivals", ["stationary", "nhpp"])
def test_deferred_short_sweep_raises_at_result(arrivals):
    kw = dict(sim_time=1000.0)
    if arrivals == "nhpp":
        kw["arrival_process"] = NHPPArrivalProcess(profile=PROFILE)
    pending = sweep(base_scn(**kw), over={"expiration_threshold": [60.0]},
                    key=jax.random.key(0), replicas=2, steps=300,
                    deferred=True)
    with pytest.raises(RuntimeError, match="before sim_time"):
        pending.result()


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------


def test_resweep_at_new_rates_does_not_retrace():
    over = lambda rates: {"expiration_threshold": [60.0, 600.0],
                          "arrival_rate": rates}
    key = jax.random.key(4)
    sweep(base_scn(), over=over([0.5, 1.5]), key=key, replicas=2, steps=1000)
    snap = dict(TRACE_COUNTS)
    sweep(base_scn(), over=over([0.7, 1.1]), key=key, replicas=2, steps=1000)
    assert TRACE_COUNTS["stage_draw_cells"] == snap["stage_draw_cells"]
    assert TRACE_COUNTS["simulate_sweep"] == snap["simulate_sweep"]


def test_online_tick_with_refit_profile_does_not_retrace():
    svc = OnlineWhatIfService(
        base_scn(sim_time=1e5),
        OnlineConfig(rate_ceiling=4.0, n_bins=4, bin_width=25.0,
                     thresholds=(30.0, 600.0), replicas=2),
    )
    rng = np.random.default_rng(3)
    recs, deltas = [], []
    for i, rate in enumerate((0.8, 2.0, 1.2)):
        n = rng.poisson(rate * 25.0) + 1
        svc.observe(np.sort(i * 25.0 + rng.uniform(0.0, 25.0, n)))
        snap = dict(TRACE_COUNTS)
        recs.append(svc.tick())
        deltas.append((
            TRACE_COUNTS["stage_draw_cells"] - snap.get("stage_draw_cells", 0),
            TRACE_COUNTS["online_tick"] - snap.get("online_tick", 0),
        ))
    recs.append(svc.flush())
    assert recs[0] is None  # overlapped: each tick drains the one before
    assert len({r.profile.rates for r in recs[1:]}) == 3  # the fit moved
    assert deltas[1:] == [(0, 0), (0, 0)]
