"""ServerlessSimulator: vectorised scale-per-request FaaS simulation.

Semantics (faithful to the paper / original ``simfaas``):

* An arrival with at least one *idle* instance is a **warm start** served by
  the **newest** idle instance (max creation time — priority routing,
  McGrath & Brenner 2017).
* Otherwise, if the live-instance count is below the *maximum concurrency
  level*, a new instance is created (**cold start**) and serves the request
  (cold response time includes provisioning).
* Otherwise the request is **rejected**.
* An instance that stays idle for ``expiration_threshold`` seconds after
  finishing its last request is terminated; tie with an arrival at the exact
  same instant resolves expire-first (probability-zero for continuous
  arrival processes).

TPU-native re-architecture (see DESIGN.md §2): one ``lax.scan`` step per
*arrival*; between consecutive arrivals every instance's trajectory
(running → idle → expired) is closed-form, so exact time-integrals of the
running/idle/total instance counts — the billing- and cost-relevant
quantities — are accumulated analytically.  The sample path is *identical*
to the event-driven original given the same random draws (cross-validated
seed-exactly against ``core/pyref.py``).

Compile-time vs run-time split (DESIGN.md §3): only the *structure* of the
computation — pool size, routing policy, unroll factor, histogram shape —
is a static jit argument (``StaticConfig``).  Workload parameters (arrival
rate via the pre-drawn samples, expiration threshold, horizon, warm-up) are
traced run-time values carried in the ``WorkloadParams`` pytree, so a whole
(rate × threshold) what-if grid shares ONE compiled executable
(``_simulate_sweep``) instead of recompiling per cell.

State layout per replica (struct-of-arrays over ``slots``):
  ``alive``      bool[M]   instance exists
  ``creation``   f64[M]    creation timestamp (routing priority)
  ``busy_until`` f64[M]    finish time of the last assigned request; the
                           instance is running until then, idle afterwards,
                           and expires at ``busy_until + expiration_threshold``
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution import register_backend, register_engine
from repro.core.processes import (
    RAW_DRAWS,
    ArrivalTimeProcess,
    ExpSimProcess,
    SimProcess,
    absolute_times_from_gaps,
    finish_sample,
    piecewise_rate,
    thin_arrivals,
)
from repro.core.reliability import NO_CHILD, build_attempt_table

# The config machinery lives in repro.core.scenario (the unified Scenario
# API); re-exported here for the engines and for pre-Scenario import paths.
from repro.core.scenario import (  # noqa: F401
    Scenario,
    StaticConfig,
    TRACE_COUNTS,
    WorkloadParams,
)

Array = jax.Array

_NEG_INF = -1e30


@dataclasses.dataclass
class WindowedMetrics:
    """Per-window metrics over a user time grid (non-stationary runs).

    Request counts are taken per arrival-window (half-open ``[b_w, b_w+1)``
    membership of the arrival instant); instance-time integrals are exact
    over each window intersected with ``[0, sim_time]``.  Windows ignore
    ``skip_time`` — the grid itself says what the user wants to see.
    """

    bounds: np.ndarray  # [W+1] window boundaries
    n_cold: np.ndarray  # [R, W]
    n_warm: np.ndarray  # [R, W]
    n_arrivals: np.ndarray  # [R, W] (includes rejected arrivals)
    time_running: np.ndarray  # [R, W] exact integral per window
    time_idle: np.ndarray  # [R, W]
    n_fail: np.ndarray = None  # [R, W] timeouts+failures (reliability runs)

    @property
    def widths(self) -> np.ndarray:
        return np.diff(self.bounds)

    @property
    def failure_prob(self) -> np.ndarray:
        """[W] pooled timeouts+failures per served request.

        Zeros when reliability is off (scan engine only — the block
        kernels track aggregate reliability columns, not per-window ones).
        """
        if self.n_fail is None:
            return np.zeros(len(self.bounds) - 1)
        served = (self.n_cold + self.n_warm).sum(axis=0)
        return self.n_fail.sum(axis=0) / np.maximum(served, 1)

    @property
    def cold_start_prob(self) -> np.ndarray:
        """[W] pooled-over-replicas per-window cold-start probability."""
        served = (self.n_cold + self.n_warm).sum(axis=0)
        return self.n_cold.sum(axis=0) / np.maximum(served, 1)

    @property
    def arrival_rate(self) -> np.ndarray:
        """[W] mean observed arrivals per second per window."""
        return self.n_arrivals.mean(axis=0) / self.widths

    @property
    def avg_instance_count(self) -> np.ndarray:
        """[W] replica-mean of total (running+idle) instance count."""
        return (self.time_running + self.time_idle).mean(axis=0) / self.widths

    @property
    def avg_running_count(self) -> np.ndarray:
        return self.time_running.mean(axis=0) / self.widths

    def to_dict(self) -> dict:
        return {
            "bounds": self.bounds.tolist(),
            "cold_start_prob": self.cold_start_prob.tolist(),
            "arrival_rate": self.arrival_rate.tolist(),
            "avg_instance_count": self.avg_instance_count.tolist(),
        }


@dataclasses.dataclass
class SimulationSummary:
    """Aggregated results.  Per-replica arrays retained for CIs."""

    n_cold: np.ndarray
    n_warm: np.ndarray
    n_reject: np.ndarray
    time_running: np.ndarray  # integral of running-instance count (s)
    time_idle: np.ndarray
    sum_cold_resp: np.ndarray
    sum_warm_resp: np.ndarray
    lifespan_sum: np.ndarray
    lifespan_count: np.ndarray
    measured_time: float
    histogram: Optional[np.ndarray] = None  # [R, hist_bins] time at count=k
    overflow: Optional[np.ndarray] = None
    windows: Optional[WindowedMetrics] = None  # set when window_bounds given
    # ---- reliability counters (None unless Scenario.reliability is set) --
    n_timeout: Optional[np.ndarray] = None  # served but cut at t_timeout
    n_fail: Optional[np.ndarray] = None  # served, completed, then failed
    n_retry: Optional[np.ndarray] = None  # re-enqueued attempts processed
    n_abandon: Optional[np.ndarray] = None  # gave up (retry budget spent)
    # ---- platform-fault counters (None unless Scenario.faults is set) ----
    n_crash: Optional[np.ndarray] = None  # instances lost to the crash hazard
    n_evict: Optional[np.ndarray] = None  # idle instances evicted by churn
    n_interrupt: Optional[np.ndarray] = None  # served attempts cut by a crash

    # ---- paper metrics -------------------------------------------------
    @property
    def n_requests(self) -> np.ndarray:
        """Processed attempts per replica (retries count individually)."""
        return self.n_cold + self.n_warm + self.n_reject

    def _rely(self, x) -> np.ndarray:
        return np.zeros_like(np.asarray(self.n_cold)) if x is None else x

    # ---- reliability metrics -------------------------------------------
    @property
    def n_attempts(self) -> np.ndarray:
        """Alias of ``n_requests`` emphasising attempts vs completions."""
        return self.n_requests

    @property
    def n_completions(self) -> np.ndarray:
        """Served attempts that neither timed out, failed, nor were
        interrupted by an instance crash."""
        return (
            self.n_cold
            + self.n_warm
            - self._rely(self.n_timeout)
            - self._rely(self.n_fail)
            - self._rely(self.n_interrupt)
        )

    @property
    def timeout_prob(self) -> float:
        served = (self.n_cold + self.n_warm).sum()
        return float(self._rely(self.n_timeout).sum() / np.maximum(served, 1))

    @property
    def failure_prob(self) -> float:
        served = (self.n_cold + self.n_warm).sum()
        return float(self._rely(self.n_fail).sum() / np.maximum(served, 1))

    @property
    def goodput(self) -> float:
        """Successful completions per second (replica mean)."""
        return float(self.n_completions.mean() / max(self.measured_time, 1e-12))

    @property
    def interrupt_prob(self) -> float:
        """Served attempts cut short by an instance crash, per served."""
        served = (self.n_cold + self.n_warm).sum()
        return float(
            self._rely(self.n_interrupt).sum() / np.maximum(served, 1)
        )

    @property
    def availability(self) -> float:
        """Fraction of served attempts the platform carried to completion
        without losing the instance underneath them: 1 − interrupt_prob.
        1.0 when no fault model is active."""
        return 1.0 - self.interrupt_prob

    @property
    def retry_amplification(self) -> float:
        """Attempts per original request — the retry-amplified load."""
        attempts = self.n_requests.sum()
        firsts = attempts - self._rely(self.n_retry).sum()
        return float(attempts / np.maximum(firsts, 1))

    @property
    def cold_start_prob(self) -> float:
        served = self.n_cold + self.n_warm
        return float(self.n_cold.sum() / np.maximum(served.sum(), 1))

    @property
    def rejection_prob(self) -> float:
        return float(self.n_reject.sum() / np.maximum(self.n_requests.sum(), 1))

    @property
    def avg_running_count(self) -> float:
        return float(self.time_running.mean() / self.measured_time)

    @property
    def avg_idle_count(self) -> float:
        return float(self.time_idle.mean() / self.measured_time)

    @property
    def avg_server_count(self) -> float:
        return self.avg_running_count + self.avg_idle_count

    @property
    def avg_lifespan(self) -> float:
        return float(self.lifespan_sum.sum() / np.maximum(self.lifespan_count.sum(), 1))

    @property
    def avg_response_time(self) -> float:
        served = np.maximum((self.n_cold + self.n_warm).sum(), 1)
        return float((self.sum_cold_resp + self.sum_warm_resp).sum() / served)

    @property
    def avg_wasted_ratio(self) -> float:
        """Idle / total instance-time — the provider's wasted capacity."""
        total = self.time_running + self.time_idle
        return float((self.time_idle.sum()) / np.maximum(total.sum(), 1e-12))

    @property
    def utilization(self) -> float:
        return 1.0 - self.avg_wasted_ratio

    def cold_start_prob_ci(self, z: float = 1.96) -> tuple[float, float]:
        """Normal-approx CI over replicas (paper Fig. 4 methodology)."""
        served = np.maximum(self.n_cold + self.n_warm, 1)
        p = self.n_cold / served
        se = p.std(ddof=1) / np.sqrt(len(p)) if len(p) > 1 else 0.0
        return float(p.mean() - z * se), float(p.mean() + z * se)

    def to_dict(self) -> dict:
        return {
            "cold_start_prob": self.cold_start_prob,
            "rejection_prob": self.rejection_prob,
            "avg_server_count": self.avg_server_count,
            "avg_running_count": self.avg_running_count,
            "avg_idle_count": self.avg_idle_count,
            "avg_lifespan": self.avg_lifespan,
            "avg_response_time": self.avg_response_time,
            "avg_wasted_ratio": self.avg_wasted_ratio,
            "n_requests": int(self.n_requests.sum()),
            "n_completions": int(self.n_completions.sum()),
            "n_timeouts": int(self._rely(self.n_timeout).sum()),
            "n_failures": int(self._rely(self.n_fail).sum()),
            "n_retries": int(self._rely(self.n_retry).sum()),
            "n_abandoned": int(self._rely(self.n_abandon).sum()),
            "goodput": self.goodput,
            "retry_amplification": self.retry_amplification,
            "n_crashes": int(self._rely(self.n_crash).sum()),
            "n_evictions": int(self._rely(self.n_evict).sum()),
            "n_interrupted": int(self._rely(self.n_interrupt).sum()),
            "availability": self.availability,
        }


# ---------------------------------------------------------------------------
# Closed-form interval integration (shared with temporal/par simulators)
# ---------------------------------------------------------------------------


def interval_integrals(alive, busy_until, exp_threshold, lo, hi):
    """Exact ∫running and ∫idle instance-counts over window (lo, hi].

    Per live slot: running on (lo, min(busy, hi)], idle on
    (max(busy, lo), min(busy + T_exp, hi)].  Window may be empty (lo >= hi).
    """
    expire = busy_until + exp_threshold
    run_t = jnp.clip(jnp.minimum(busy_until, hi) - lo, 0.0, None)
    idle_t = jnp.clip(
        jnp.minimum(expire, hi) - jnp.maximum(busy_until, lo), 0.0, None
    )
    run_t = jnp.where(alive, run_t, 0.0)
    idle_t = jnp.where(alive, idle_t, 0.0)
    return run_t.sum(), idle_t.sum()


def fault_interval_integrals(alive, busy_until, exp_threshold, doom, lo, hi):
    """:func:`interval_integrals` under a crash hazard: per-slot accrual
    stops at the instance's crash time ``doom`` (the slot is removed at
    the next event, but it stops existing — and billing — at ``doom``)."""
    expire = busy_until + exp_threshold
    stop = jnp.minimum(hi, doom)
    run_t = jnp.clip(jnp.minimum(busy_until, stop) - lo, 0.0, None)
    idle_t = jnp.clip(
        jnp.minimum(expire, stop) - jnp.maximum(busy_until, lo), 0.0, None
    )
    run_t = jnp.where(alive, run_t, 0.0)
    idle_t = jnp.where(alive, idle_t, 0.0)
    return run_t.sum(), idle_t.sum()


def histogram_update(hist, alive, busy_until, exp_threshold, lo, hi):
    """Accumulate time spent at each total-instance-count within (lo, hi].

    Between arrivals the count only decreases, at each slot's expiry time.
    Sort expiry times inside the window; segment k (between consecutive
    order statistics) has count n0 - k.
    """
    window = jnp.maximum(hi - lo, 0.0)
    expire = jnp.where(alive, busy_until + exp_threshold, _NEG_INF)
    n0 = (expire > lo).sum()  # live at window start
    # Expiries inside the window; non-events map to hi (zero-length tail).
    ev = jnp.where((expire > lo) & (expire <= hi), expire, hi)
    ev = jnp.where(window > 0.0, ev, hi)
    ev_sorted = jnp.sort(ev)
    bounds = jnp.concatenate([jnp.array([0.0], dtype=ev.dtype) + lo, ev_sorted])
    nxt = jnp.concatenate([ev_sorted, jnp.array([0.0], dtype=ev.dtype) + hi])
    durations = jnp.clip(nxt - bounds, 0.0, None)
    durations = jnp.where(window > 0.0, durations, 0.0)
    counts = n0 - jnp.arange(bounds.shape[0])
    # The padded-``hi`` tail yields segments with counts < 0 (more expiries
    # sorted than live instances).  Those segments are zero-length by
    # construction, but clipping their index into bin 0 would silently
    # credit time-at-count-0 if a caller ever passes an inconsistent pool
    # (e.g. stale ``alive`` flags) — mask them out instead of clipping.
    valid = (counts >= 0) & (durations > 0.0)
    durations = jnp.where(valid, durations, 0.0)
    idx = jnp.clip(counts, 0, hist.shape[0] - 1)
    return hist.at[idx].add(durations)


# ---------------------------------------------------------------------------
# Sample drawing (shared by ServerlessSimulator / temporal / par engines)
# ---------------------------------------------------------------------------


def check_coverage(covered, sim_time, *, stamped: bool) -> None:
    """Raise where an arrival row ends before its horizon.

    ``covered`` is how far each row's arrivals reach — a timestamp
    stream's coverage (``stamped``), else the final clock or the f64 sum
    of a row's gaps — and ``sim_time`` the horizon, a scalar or one per
    row.  A short row would silently truncate its cell.
    """
    covered = np.asarray(covered)
    if not (covered < sim_time).any():
        return
    if stamped:
        raise RuntimeError(
            "arrival-stream coverage ended before sim_time "
            f"(min coverage {covered.min():.1f} < {sim_time}); "
            "pass a larger `steps`"
        )
    raise RuntimeError(
        "pre-drawn arrivals ended before sim_time "
        f"(min final t {covered.min():.1f}); pass a larger `steps`"
    )


def draw_workload_samples(cfg: Scenario, key: Array, replicas: int, n: int):
    """Draw the (arrivals, warm, cold) sample buffers for ``n`` steps.

    Stationary arrival processes yield f32 ``[R, n]`` inter-arrival gaps;
    :class:`ArrivalTimeProcess` arrivals (NHPP, exact trace replay) yield
    f64 ``[R, n]`` absolute timestamps for the prestamped scan, with a
    host-side coverage guard — a padded timestamp stream ends in
    ``PAD_TIME`` so the engines' final-clock check cannot detect
    under-coverage, the generating process has to report it.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    ap = cfg.arrival_process
    if isinstance(ap, ArrivalTimeProcess):
        arr, coverage = ap.arrival_times(k1, (replicas, n))
        check_coverage(coverage, cfg.sim_time, stamped=True)
    else:
        arr = ap.sample(k1, (replicas, n))
    warms = cfg.warm_service_process.sample(k2, (replicas, n))
    colds = cfg.cold_service_process.sample(k3, (replicas, n))
    return arr, warms, colds


# fold_in salts for the reliability side-draws: the base (arrival, warm,
# cold) draws keep the exact ``split(key, 3)`` schedule above, so enabling
# a trivial reliability policy replays the base stream bitwise.
_RELY_SALT_JITTER = 1013
_RELY_SALT_WARM = 1014
_RELY_SALT_COLD = 1015
_RELY_SALT_FAIL = 1016


@functools.partial(
    jax.jit, static_argnames=("kinds", "replicas", "n", "fail")
)
def stage_draw_cells(key, params, *, kinds, replicas, n, fail=False):
    """A sweep's draw cells staged in one compiled device program.

    The batched form of calling :func:`draw_workload_samples` once per
    cell along the sweep's chained ``key, sub = split(key)`` walk (with
    :func:`draw_reliability_stream`'s failure uniforms when ``fail``):
    bitwise the same draws, from one device program with no host sync.
    ``kinds`` and ``params`` are what ``drawplan.lower_staged`` made of
    the cells.  Returns ``(samples, coverage)``: the ``[D·R, n]`` sample
    stacks and each row's coverage ``[D·R]`` reduced on the device — the
    f64 sum of its f32 gaps, or an NHPP row's last candidate time — for
    the caller to check against the horizon once it is fetched.  One
    compile per (families, D, R, n, profile steps); every parameter is
    traced.
    """
    TRACE_COUNTS["stage_draw_cells"] += 1
    shape = (replicas, n)
    arrival, warm, cold = kinds

    def cell(key, p):
        key, sub = jax.random.split(key)
        k1, k2, k3 = jax.random.split(sub, 3)
        if arrival == "nhpp":
            rate = functools.partial(piecewise_rate, p["edges"], p["rates"])
            arr, cover = thin_arrivals(k1, shape, p["arrival"][0], rate)
        else:
            arr = finish_sample(RAW_DRAWS[arrival](k1, shape, *p["arrival"]))
            cover = arr.astype(jnp.float64).sum(axis=1)
        out = (
            arr,
            finish_sample(RAW_DRAWS[warm](k2, shape, *p["warm"])),
            finish_sample(RAW_DRAWS[cold](k3, shape, *p["cold"])),
        )
        if fail:
            kf = jax.random.fold_in(sub, _RELY_SALT_FAIL)
            out += (jax.random.uniform(kf, shape, dtype=jnp.float32),)
        return key, (out, cover)

    _, (out, cover) = jax.lax.scan(cell, key, params)
    return tuple(x.reshape((-1, n)) for x in out), cover.reshape(-1)


def draw_crash_uniforms(key: Array, replicas: int, n: int):
    """Per-event crash-lifetime uniforms for the fault layer.

    Drawn from ``fold_in(key, CRASH_SALT)`` (salt 1017, continuing the
    reliability chain above), so enabling a trivial :class:`FaultModel`
    leaves every base and reliability stream bitwise unchanged.  ``n``
    must match the event-stream width the engine consumes (the attempt
    table's ``n·(J+1)`` under retries).
    """
    from repro.core.faults import CRASH_SALT

    kx = jax.random.fold_in(key, CRASH_SALT)
    return jax.random.uniform(kx, (replicas, n), dtype=jnp.float32)


def draw_reliability_stream(cfg: Scenario, key: Array, replicas: int, n: int):
    """Draw ``(arrivals, warm, cold)`` plus the reliability extras.

    Returns ``(samples, extras)``.  With ``max_retries == 0`` the native
    stream is kept and ``extras = (fail_u,)``.  With retries, the sorted
    per-attempt table replaces the stream (absolute f64 times — the scan
    runs prestamped) and ``extras = (fail_u, is_first, child_pos)``; the
    table is built host-side once, so the f64 scan, the f32 block kernels
    and the pure-Python oracle all replay identical events.
    """
    rel = cfg.reliability
    arr, warms, colds = draw_workload_samples(cfg, key, replicas, n)
    if rel is None:
        return (arr, warms, colds), ()
    J = int(rel.retry.max_retries)
    kf = jax.random.fold_in(key, _RELY_SALT_FAIL)
    if J == 0:
        fail_u = jax.random.uniform(kf, (replicas, n), dtype=jnp.float32)
        return (arr, warms, colds), (fail_u,)
    if cfg.prestamped:
        times0 = jnp.asarray(arr, jnp.float64)
    else:
        # The gap stream becomes absolute timestamps; its final-clock
        # coverage check can no longer run inside the engines, so guard
        # here (f64 sum of the f32 gaps, as on the block paths).
        covered = np.asarray(arr, np.float64).sum(axis=1)
        if (covered < cfg.sim_time).any():
            raise RuntimeError(
                "pre-drawn arrivals ended before sim_time "
                f"(min final t {covered.min():.1f} < {cfg.sim_time}); "
                "pass a larger `steps`"
            )
        times0 = absolute_times_from_gaps(arr)
    kj = jax.random.fold_in(key, _RELY_SALT_JITTER)
    kw = jax.random.fold_in(key, _RELY_SALT_WARM)
    kc = jax.random.fold_in(key, _RELY_SALT_COLD)
    jitter_u = jax.random.uniform(kj, (replicas, n, J), dtype=jnp.float64)
    warms_x = cfg.warm_service_process.sample(kw, (replicas, n * J))
    colds_x = cfg.cold_service_process.sample(kc, (replicas, n * J))
    fail_a = jax.random.uniform(kf, (replicas, n, J + 1), dtype=jnp.float32)
    warms_a = jnp.concatenate(
        [warms[:, :, None], warms_x.reshape(replicas, n, J)], axis=2
    )
    colds_a = jnp.concatenate(
        [colds[:, :, None], colds_x.reshape(replicas, n, J)], axis=2
    )
    times, warms_s, colds_s, fail_s, first_s, child_s = build_attempt_table(
        times0, warms_a, colds_a, fail_a, jitter_u, rel.retry
    )
    return (times, warms_s, colds_s), (fail_s, first_s, child_s)


# ---------------------------------------------------------------------------
# Single-replica scan
# ---------------------------------------------------------------------------


def _window_integrals(bounds, alive, busy_until, t_exp, lo_eff, hi_eff):
    """Exact per-window ∫running / ∫idle over (lo_eff, hi_eff] ∩ window."""
    wlo = jnp.maximum(bounds[:-1], lo_eff)
    whi = jnp.minimum(bounds[1:], hi_eff)
    return jax.vmap(
        lambda l, h: interval_integrals(alive, busy_until, t_exp, l, h)
    )(wlo, whi)


def _make_scan_fn(cfg: StaticConfig, params: WorkloadParams, thin=None):
    """The per-arrival step function.

    ``thin=(profile, lam)`` arms inline NHPP thinning for the fused draw
    path: ``xs`` then carries an extra acceptance uniform after the cold
    sample, and a candidate is *rejected* (made an inert no-op arrival —
    it still advances the clock, integrates and expires, which interval
    additivity keeps exact) when ``u · lam > profile.rate(t)``.
    """
    t_exp = params.expiration_threshold
    t_end = params.sim_time
    skip = params.skip_time
    max_c = cfg.max_concurrency
    rely = cfg.reliability
    retries = cfg.max_retries > 0
    crashes = cfg.crashes
    capped = cfg.cap_steps > 0

    def step(state, xs):
        if crashes:
            (alive, creation, busy_until, doom, t_prev, acc) = state
        else:
            (alive, creation, busy_until, t_prev, acc) = state
            doom = None
        u_acc = None
        crash_u = None
        if retries:
            # Attempt-table stream: per-event failure uniform, first-attempt
            # flag, retry-successor position and the event's own position.
            if crashes:
                dt, warm_s, cold_s, fail_u, is_first, child_pos, crash_u, pos = xs
            else:
                dt, warm_s, cold_s, fail_u, is_first, child_pos, pos = xs
        elif thin is not None and rely:
            dt, warm_s, cold_s, u_acc, fail_u = xs
        elif thin is not None:
            dt, warm_s, cold_s, u_acc = xs
        elif rely and crashes:
            dt, warm_s, cold_s, fail_u, crash_u = xs
        elif rely:
            dt, warm_s, cold_s, fail_u = xs
        elif crashes:
            dt, warm_s, cold_s, crash_u = xs
        else:
            dt, warm_s, cold_s = xs
        if cfg.prestamped:
            # xs carries the absolute arrival timestamp (f64), not a gap.
            t = dt.astype(jnp.float64)
        else:
            t = t_prev + dt.astype(jnp.float64)

        # ---- exact integrals over the measurement window of this interval
        lo = jnp.clip(t_prev, skip, t_end)
        hi = jnp.clip(t, skip, t_end)
        if crashes:
            run_t, idle_t = fault_interval_integrals(
                alive, busy_until, t_exp, doom, lo, hi
            )
        else:
            run_t, idle_t = interval_integrals(
                alive, busy_until, t_exp, lo, hi
            )

        if cfg.n_windows:
            run_w, idle_w = _window_integrals(
                params.window_bounds,
                alive,
                busy_until,
                t_exp,
                jnp.minimum(t_prev, t_end),
                jnp.minimum(t, t_end),
            )
        if cfg.track_histogram:
            hist = histogram_update(acc["hist"], alive, busy_until, t_exp, lo, hi)
        else:
            hist = acc["hist"]

        # ---- expirations strictly before (or at) the arrival
        expire_time = busy_until + t_exp
        if crashes:
            # An instance exits at min(expiry, crash); a strictly earlier
            # doom classifies the exit as a crash (tie resolves expiry).
            exit_time = jnp.minimum(expire_time, doom)
            exited_now = alive & (exit_time <= t)
            crash_ok = (
                exited_now
                & (doom < expire_time)
                & (doom > skip)
                & (doom <= t_end)
            )
            n_crash_inc = crash_ok.sum()
            lifespan_ok = (
                exited_now & (exit_time > skip) & (exit_time <= t_end)
            )
            lifespan_sum = acc["lifespan_sum"] + jnp.where(
                lifespan_ok, exit_time - creation, 0.0
            ).sum()
            lifespan_count = acc["lifespan_count"] + lifespan_ok.sum()
            alive = alive & ~exited_now
        else:
            expired_now = alive & (expire_time <= t)
            lifespan_ok = (
                expired_now & (expire_time > skip) & (expire_time <= t_end)
            )
            lifespan_sum = acc["lifespan_sum"] + jnp.where(
                lifespan_ok, expire_time - creation, 0.0
            ).sum()
            lifespan_count = acc["lifespan_count"] + lifespan_ok.sum()
            alive = alive & ~expired_now

        # ---- capacity churn: evict newest idle instances over the ceiling
        if capped:
            cap_now = params.cap_values[
                jnp.searchsorted(params.cap_edges, t, side="right")
            ]
            idle_now = alive & (busy_until <= t)
            over = alive.sum().astype(jnp.float64) - cap_now
            slot_ids = jnp.arange(alive.shape[0])
            newer = (creation[None, :] > creation[:, None]) | (
                (creation[None, :] == creation[:, None])
                & (slot_ids[None, :] < slot_ids[:, None])
            )
            rank = (idle_now[None, :] & newer).sum(axis=1)
            evict = (
                idle_now & (rank.astype(jnp.float64) < over) & (t <= t_end)
            )
            evict_ok = evict & (t > skip)
            n_evict_inc = evict_ok.sum()
            lifespan_sum = lifespan_sum + jnp.where(
                evict_ok, t - creation, 0.0
            ).sum()
            lifespan_count = lifespan_count + evict_ok.sum()
            alive = alive & ~evict

        # ---- routing
        active = t <= t_end
        if thin is not None:
            profile, lam = thin
            active = active & (
                u_acc.astype(jnp.float64) * lam <= profile.rate(t)
            )
        if retries:
            # Non-first attempts stay inert until their parent's failure /
            # timeout / rejection switches them on; inactive events still
            # advance the clock, integrate, and expire (interval
            # additivity keeps that exact) — they are no-op arrivals.
            act = acc["act"]
            active = active & (is_first | act[pos])
        idle_mask = alive & (busy_until <= t)
        any_idle = idle_mask.any()
        # priority by creation time: newest (paper) or oldest
        priority = creation if cfg.routing == "newest" else -creation
        warm_idx = jnp.argmax(jnp.where(idle_mask, priority, _NEG_INF))
        free_mask = ~alive
        any_free = free_mask.any()
        free_idx = jnp.argmax(free_mask)  # first free slot
        n_alive = alive.sum()

        can_cold = (~any_idle) & (n_alive < max_c) & any_free
        if capped:
            # admission gate while degraded: no cold start over the ceiling
            can_cold = can_cold & (n_alive.astype(jnp.float64) < cap_now)
        overflow = (~any_idle) & (n_alive < max_c) & (~any_free) & active
        is_warm = any_idle & active
        is_cold = can_cold & active
        is_reject = (~any_idle) & (~can_cold) & active

        chosen = jnp.where(is_warm, warm_idx, free_idx)
        service = jnp.where(is_warm, warm_s, cold_s).astype(jnp.float64)
        assign = is_warm | is_cold
        if rely:
            # The instance is freed at min(departure, t_arrival + t_timeout)
            # — the sentinel NO_TIMEOUT (1e30) makes min() the identity, so
            # an enabled-but-trivial policy stays bitwise-exact.
            occupancy = jnp.minimum(service, params.t_timeout)
        else:
            occupancy = service
        new_busy = jnp.where(assign, t + occupancy, busy_until[chosen])
        busy_until = busy_until.at[chosen].set(new_busy)
        new_creation = jnp.where(is_cold, t, creation[chosen])
        creation = creation.at[chosen].set(new_creation)
        alive = alive.at[chosen].set(alive[chosen] | is_cold)
        if crashes:
            # A cold start draws the instance's Exp(crash_rate) lifetime
            # from its pre-drawn uniform (memoryless hazard); warm serves
            # keep the instance's existing doom.
            life = (
                -jnp.log(1.0 - crash_u.astype(jnp.float64))
                / params.crash_rate
            )
            doom_chosen = jnp.where(is_cold, t + life, doom[chosen])
            doom = doom.at[chosen].set(doom_chosen)

        counted = t > skip  # warm-up exclusion for request-level metrics
        if rely:
            # A timed-out attempt was cut at t_timeout; a failed one ran to
            # completion and then failed (pre-drawn per-attempt uniform).
            # Response-time sums bill the actual occupancy.
            timed_out = assign & (service > params.t_timeout)
            failed = (
                assign
                & ~timed_out
                & (fail_u.astype(jnp.float64) < params.p_fail)
            )
            if crashes:
                # The serving instance dies before the attempt completes:
                # the attempt is interrupted — a platform-side failure the
                # retry path replays like any other trigger.
                interrupted = (
                    assign
                    & ~timed_out
                    & ~failed
                    & (doom_chosen < t + occupancy)
                )
                trigger = timed_out | failed | interrupted | is_reject
            else:
                trigger = timed_out | failed | is_reject
            cold_resp = jnp.minimum(cold_s.astype(jnp.float64), params.t_timeout)
            warm_resp = jnp.minimum(warm_s.astype(jnp.float64), params.t_timeout)
        else:
            if crashes:
                interrupted = assign & (doom_chosen < t + occupancy)
            cold_resp, warm_resp = cold_s, warm_s
        acc = dict(
            n_cold=acc["n_cold"] + (is_cold & counted),
            n_warm=acc["n_warm"] + (is_warm & counted),
            n_reject=acc["n_reject"] + (is_reject & counted),
            time_running=acc["time_running"] + run_t,
            time_idle=acc["time_idle"] + idle_t,
            sum_cold_resp=acc["sum_cold_resp"]
            + jnp.where(is_cold & counted, cold_resp, 0.0),
            sum_warm_resp=acc["sum_warm_resp"]
            + jnp.where(is_warm & counted, warm_resp, 0.0),
            lifespan_sum=lifespan_sum,
            lifespan_count=lifespan_count,
            overflow=acc["overflow"] + overflow,
            hist=hist,
            w_cold=acc["w_cold"],
            w_warm=acc["w_warm"],
            w_arrivals=acc["w_arrivals"],
            w_run_t=acc["w_run_t"],
            w_idle_t=acc["w_idle_t"],
            n_timeout=acc["n_timeout"],
            n_fail=acc["n_fail"],
            n_retry=acc["n_retry"],
            n_abandon=acc["n_abandon"],
            w_fail=acc["w_fail"],
            n_crash=acc["n_crash"],
            n_evict=acc["n_evict"],
            n_interrupt=acc["n_interrupt"],
        )
        if crashes:
            acc["n_crash"] = acc["n_crash"] + n_crash_inc
            acc["n_interrupt"] = acc["n_interrupt"] + (interrupted & counted)
        if capped:
            acc["n_evict"] = acc["n_evict"] + n_evict_inc
        if rely:
            acc["n_timeout"] = acc["n_timeout"] + (timed_out & counted)
            acc["n_fail"] = acc["n_fail"] + (failed & counted)
            if retries:
                has_child = child_pos < NO_CHILD
                acc["n_retry"] = acc["n_retry"] + (
                    ~is_first & active & counted
                )
                acc["n_abandon"] = acc["n_abandon"] + (
                    trigger & ~has_child & counted
                )
                # Re-enqueue: switch on the retry successor.  Out-of-bounds
                # sentinel positions are dropped by the scatter.
                child_c = jnp.minimum(child_pos, act.shape[0] - 1)
                acc["act"] = act.at[child_pos].set(
                    act[child_c] | trigger, mode="drop"
                )
            else:
                acc["n_abandon"] = acc["n_abandon"] + (trigger & counted)
        if cfg.n_windows:
            # half-open window membership [b_w, b_{w+1}) of the arrival
            # instant; windows deliberately ignore skip_time (the grid is
            # the user's own measurement request).
            w_idx = (
                jnp.searchsorted(params.window_bounds, t, side="right") - 1
            )
            onehot = (jnp.arange(cfg.n_windows) == w_idx) & active
            acc["w_cold"] = acc["w_cold"] + (onehot & is_cold)
            acc["w_warm"] = acc["w_warm"] + (onehot & is_warm)
            acc["w_arrivals"] = acc["w_arrivals"] + onehot
            acc["w_run_t"] = acc["w_run_t"] + run_w
            acc["w_idle_t"] = acc["w_idle_t"] + idle_w
            if rely:
                acc["w_fail"] = acc["w_fail"] + (
                    onehot & (timed_out | failed)
                )
        if crashes:
            return (alive, creation, busy_until, doom, t, acc), None
        return (alive, creation, busy_until, t, acc), None

    return step


def _empty_acc(cfg: StaticConfig):
    z = jnp.zeros((), dtype=jnp.float64)
    zi = jnp.zeros((), dtype=jnp.int64)
    return dict(
        n_cold=zi,
        n_warm=zi,
        n_reject=zi,
        time_running=z,
        time_idle=z,
        sum_cold_resp=z,
        sum_warm_resp=z,
        lifespan_sum=z,
        lifespan_count=zi,
        overflow=zi,
        hist=jnp.zeros((cfg.hist_bins,), dtype=jnp.float64),
        w_cold=jnp.zeros((cfg.n_windows,), dtype=jnp.int64),
        w_warm=jnp.zeros((cfg.n_windows,), dtype=jnp.int64),
        w_arrivals=jnp.zeros((cfg.n_windows,), dtype=jnp.int64),
        w_run_t=jnp.zeros((cfg.n_windows,), dtype=jnp.float64),
        w_idle_t=jnp.zeros((cfg.n_windows,), dtype=jnp.float64),
        n_timeout=zi,
        n_fail=zi,
        n_retry=zi,
        n_abandon=zi,
        w_fail=jnp.zeros((cfg.n_windows,), dtype=jnp.int64),
        n_crash=zi,
        n_evict=zi,
        n_interrupt=zi,
    )


def _empty_pool(cfg: StaticConfig):
    m = cfg.slots
    pool = (
        jnp.zeros((m,), dtype=bool),
        jnp.full((m,), _NEG_INF, dtype=jnp.float64),
        jnp.full((m,), _NEG_INF, dtype=jnp.float64),
    )
    if cfg.crashes:
        # per-slot crash time; +inf until a cold start draws a lifetime
        pool = pool + (jnp.full((m,), jnp.inf, dtype=jnp.float64),)
    return pool


def _flush(cfg: StaticConfig, params: WorkloadParams, state):
    """Integrate the tail (t_last, sim_time] after the final arrival."""
    if cfg.crashes:
        alive, creation, busy_until, doom, t_prev, acc = state
    else:
        alive, creation, busy_until, t_prev, acc = state
    t_exp = params.expiration_threshold
    lo = jnp.clip(t_prev, params.skip_time, params.sim_time)
    hi = jnp.asarray(params.sim_time, dtype=jnp.float64)
    if cfg.crashes:
        run_t, idle_t = fault_interval_integrals(
            alive, busy_until, t_exp, doom, lo, hi
        )
    else:
        run_t, idle_t = interval_integrals(alive, busy_until, t_exp, lo, hi)
    acc["time_running"] = acc["time_running"] + run_t
    acc["time_idle"] = acc["time_idle"] + idle_t
    if cfg.n_windows:
        run_w, idle_w = _window_integrals(
            params.window_bounds,
            alive,
            busy_until,
            t_exp,
            jnp.minimum(t_prev, hi),
            hi,
        )
        acc["w_run_t"] = acc["w_run_t"] + run_w
        acc["w_idle_t"] = acc["w_idle_t"] + idle_w
    if cfg.track_histogram:
        acc["hist"] = histogram_update(acc["hist"], alive, busy_until, t_exp, lo, hi)
    expire_time = busy_until + t_exp
    if cfg.crashes:
        exit_time = jnp.minimum(expire_time, doom)
        tail_exp = (
            alive & (exit_time <= hi) & (exit_time > params.skip_time)
        )
        acc["lifespan_sum"] = acc["lifespan_sum"] + jnp.where(
            tail_exp, exit_time - creation, 0.0
        ).sum()
        acc["lifespan_count"] = acc["lifespan_count"] + tail_exp.sum()
        acc["n_crash"] = acc["n_crash"] + (
            tail_exp & (doom < expire_time)
        ).sum()
        return acc, t_prev
    tail_exp = alive & (expire_time <= hi) & (expire_time > params.skip_time)
    acc["lifespan_sum"] = acc["lifespan_sum"] + jnp.where(
        tail_exp, expire_time - creation, 0.0
    ).sum()
    acc["lifespan_count"] = acc["lifespan_count"] + tail_exp.sum()
    return acc, t_prev


def _scan_one(
    cfg: StaticConfig,
    params: WorkloadParams,
    dt_row,
    warm_row,
    cold_row,
    pool0=None,
    extra_rows=(),
):
    """One replica: scan over its arrival stream, then flush the tail.

    ``extra_rows`` carries the reliability columns — ``(fail_u,)`` on a
    native stream, ``(fail_u, is_first, child_pos)`` on an attempt table
    (then the activation mask rides in the carry and the event's own
    position is appended as an iota column).
    """
    step = _make_scan_fn(cfg, params)
    pool = _empty_pool(cfg) if pool0 is None else tuple(pool0)
    if cfg.crashes and len(pool) == 3:
        # caller-provided pools predate the fault layer: no slot has drawn
        # a lifetime yet, so every doom starts at +inf
        pool = pool + (jnp.full((cfg.slots,), jnp.inf, dtype=jnp.float64),)
    acc = _empty_acc(cfg)
    xs = (dt_row, warm_row, cold_row) + tuple(extra_rows)
    if cfg.max_retries > 0:
        acc["act"] = jnp.zeros(dt_row.shape, dtype=bool)
        xs = xs + (jnp.arange(dt_row.shape[0]),)
    state0 = (*pool, jnp.zeros((), jnp.float64), acc)
    state, _ = jax.lax.scan(step, state0, xs, unroll=cfg.scan_unroll)
    acc, t_last = _flush(cfg, params, state)
    acc.pop("act", None)
    return acc, t_last


@functools.partial(jax.jit, static_argnums=(0,))
def _simulate_batch(
    cfg: StaticConfig, params: WorkloadParams, dts, warms, colds,
    init_pool=None, extras=(),
):
    """vmap over replicas of the arrival-driven scan. Inputs: f32[R, N].

    ``params`` leaves are scalars shared by every replica.
    """
    TRACE_COUNTS["simulate_batch"] += 1

    def one(dt_row, warm_row, cold_row, *ex):
        return _scan_one(
            cfg, params, dt_row, warm_row, cold_row,
            pool0=init_pool, extra_rows=ex,
        )

    return jax.vmap(one)(dts, warms, colds, *extras)


def _sweep_rows(cfg: StaticConfig, params: WorkloadParams, dts, warms, colds, *extras):
    """The unjitted sweep body: vmap the per-row scan over the flattened
    grid axis (shared by the plain, non-donating and sharded entries)."""

    def one(p, dt_row, warm_row, cold_row, *ex):
        return _scan_one(cfg, p, dt_row, warm_row, cold_row, extra_rows=ex)

    return jax.vmap(one)(params, dts, warms, colds, *extras)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def _simulate_sweep(cfg: StaticConfig, params: WorkloadParams, dts, warms, colds, *extras):
    """The single-compile what-if engine: one jitted, donated call.

    ``params`` leaves and the sample arrays all carry a leading flattened
    grid axis ``C = E·A·R`` (threshold × rate × replica); the per-replica
    scan is vmapped over it, so an entire sweep is ONE device execution and
    one trace regardless of grid size.  Sample buffers are donated — the
    grid's [C, N] f32 draws are the dominant allocation and are dead after
    the call.
    """
    TRACE_COUNTS["simulate_sweep"] += 1
    return _sweep_rows(cfg, params, dts, warms, colds, *extras)


@functools.lru_cache(maxsize=None)
def sweep_executable(mesh=None, donate: bool = True):
    """The jitted sweep entry point for an :class:`Execution` plan.

    ``mesh=None`` is the single-device engine; a 1-D ``Mesh`` (axis
    ``"grid"``) wraps the same vmapped body in ``shard_map`` so each
    device runs its contiguous slice of the flattened grid axis — rows
    are independent, so per-cell results are bitwise-identical to the
    unsharded call.  The caller pads the axis to a multiple of the device
    count.  Cached per (mesh, donate) so each variant compiles once;
    sharded traces are pinned by ``TRACE_COUNTS["simulate_sweep_sharded"]``.
    """
    if mesh is None and donate:
        return _simulate_sweep
    counter = "simulate_sweep" if mesh is None else "simulate_sweep_sharded"

    def fn(cfg, params, dts, warms, colds, *extras):
        TRACE_COUNTS[counter] += 1
        if mesh is None:
            return _sweep_rows(cfg, params, dts, warms, colds, *extras)
        from jax.sharding import PartitionSpec

        spec = PartitionSpec("grid")
        # check_vma=False: rows are independent and the body has no
        # collectives; the scan's initial carry is built from constants
        # (unvarying) while its output varies over "grid", which the
        # varying-axes type check would otherwise reject
        return jax.shard_map(
            functools.partial(_sweep_rows, cfg),
            mesh=mesh,
            in_specs=(spec,) * (4 + len(extras)),
            out_specs=spec,
            check_vma=False,
        )(params, dts, warms, colds, *extras)

    return jax.jit(
        fn,
        static_argnums=(0,),
        donate_argnums=(2, 3, 4) if donate else (),
    )


# ---------------------------------------------------------------------------
# Fused draws (DESIGN.md §12): the scan consumes a DrawPlan instead of
# pre-staged [R, K] sample buffers — every draw is generated inside the
# scan body from a counter-based threefry keyed per row/stream.
# ---------------------------------------------------------------------------


def _fused_event_xs(fplan, krow, prow, i):
    """One event's xs tuple for the staged step fn, generated inline.

    ``krow``/``prow`` are one replica row's per-stream uint32 key pairs /
    f64 param pairs; ``i`` is the global event counter.  Returns
    ``(dt, warm, cold[, u_acc][, fail_u])`` matching the unpack order of
    :func:`_make_scan_fn` (``u_acc`` present iff the arrival spec is NHPP,
    ``fail_u`` iff the plan carries the failure stream).
    """
    from repro.core import drawplan as dp

    a_u0, a_u1 = dp.event_uniforms(krow["arrival"][0], krow["arrival"][1], i)
    w_u0, w_u1 = dp.event_uniforms(krow["warm"][0], krow["warm"][1], i)
    c_u0, c_u1 = dp.event_uniforms(krow["cold"][0], krow["cold"][1], i)
    pa, pw, pc = prow["arrival"], prow["warm"], prow["cold"]
    nhpp = fplan.arrival.kind == "nhpp"
    # NHPP candidates come from the exponential envelope (rate lam = p0);
    # the second threefry word becomes the thinning-acceptance uniform.
    a_kind = "exp" if nhpp else fplan.arrival.kind
    dt = dp.sample_dist(a_kind, a_u0, a_u1, pa[0], pa[1])
    warm_s = dp.sample_dist(fplan.warm.kind, w_u0, w_u1, pw[0], pw[1])
    cold_s = dp.sample_dist(fplan.cold.kind, c_u0, c_u1, pc[0], pc[1])
    xs = (dt, warm_s, cold_s)
    if nhpp:
        xs = xs + (a_u1,)
    if fplan.fail:
        f_u0, _ = dp.event_uniforms(krow["fail"][0], krow["fail"][1], i)
        xs = xs + (f_u0,)
    return xs


def _scan_one_fused(cfg: StaticConfig, fplan, params: WorkloadParams, krow, prow, n: int):
    """One replica, fused draws: scan over the event counter, not buffers."""
    thin = None
    if fplan.arrival.kind == "nhpp":
        thin = (fplan.arrival.profile, prow["arrival"][0])
    step = _make_scan_fn(cfg, params, thin=thin)
    pool = _empty_pool(cfg)
    acc = _empty_acc(cfg)

    def fstep(state, i):
        return step(state, _fused_event_xs(fplan, krow, prow, i))

    state0 = (*pool, jnp.zeros((), jnp.float64), acc)
    state, _ = jax.lax.scan(
        fstep, state0, jnp.arange(n, dtype=jnp.uint32), unroll=cfg.scan_unroll
    )
    return _flush(cfg, params, state)


def _fused_sweep_rows(cfg, fplan, n, params, krows, prows):
    def one(p, kr, pr):
        return _scan_one_fused(cfg, fplan, p, kr, pr, n)

    return jax.vmap(one)(params, krows, prows)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _simulate_sweep_fused(cfg: StaticConfig, fplan, n: int, params, krows, prows):
    """The fused what-if engine: the whole grid in one device execution
    with O(C) inputs — per-row key pairs and distribution params — in
    place of the staged path's O(C·K) sample buffers."""
    TRACE_COUNTS["simulate_sweep_fused"] += 1
    return _fused_sweep_rows(cfg, fplan, n, params, krows, prows)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _simulate_batch_fused(cfg: StaticConfig, fplan, n: int, params, krows, prows):
    """Fused analogue of :func:`_simulate_batch`: shared scalar params,
    vmapped over per-replica key rows."""
    TRACE_COUNTS["simulate_batch_fused"] += 1

    def one(kr, pr):
        return _scan_one_fused(cfg, fplan, params, kr, pr, n)

    return jax.vmap(one)(krows, prows)


def _summarize_scan(cfg: Scenario, acc: dict, t_last) -> SimulationSummary:
    """Post-scan guards and summary assembly (staged and fused runs)."""
    rel = cfg.reliability
    if (t_last < cfg.sim_time).any():
        raise RuntimeError(
            "arrival stream ended before sim_time "
            f"(min final t {t_last.min():.1f} < {cfg.sim_time}); "
            "pass a larger `steps`"
        )
    if acc["overflow"].sum() > 0:
        raise RuntimeError(
            f"instance-pool overflow ({int(acc['overflow'].sum())} arrivals "
            f"needed a slot beyond slots={cfg.slots} while below "
            "max_concurrency); raise Scenario.slots"
        )
    windows = None
    if cfg.window_bounds:
        windows = WindowedMetrics(
            bounds=np.asarray(cfg.window_bounds),
            n_cold=acc["w_cold"],
            n_warm=acc["w_warm"],
            n_arrivals=acc["w_arrivals"],
            time_running=acc["w_run_t"],
            time_idle=acc["w_idle_t"],
            n_fail=acc["w_fail"] if rel is not None else None,
        )
    rely_kw = {}
    if rel is not None:
        rely_kw = dict(
            n_timeout=acc["n_timeout"],
            n_fail=acc["n_fail"],
            n_retry=acc["n_retry"],
            n_abandon=acc["n_abandon"],
        )
    if cfg.faults is not None:
        rely_kw.update(
            n_crash=acc["n_crash"],
            n_evict=acc["n_evict"],
            n_interrupt=acc["n_interrupt"],
        )
    return SimulationSummary(
        n_cold=acc["n_cold"],
        n_warm=acc["n_warm"],
        n_reject=acc["n_reject"],
        time_running=acc["time_running"],
        time_idle=acc["time_idle"],
        sum_cold_resp=acc["sum_cold_resp"],
        sum_warm_resp=acc["sum_warm_resp"],
        lifespan_sum=acc["lifespan_sum"],
        lifespan_count=acc["lifespan_count"],
        measured_time=cfg.sim_time - cfg.skip_time,
        histogram=acc["hist"] if cfg.track_histogram else None,
        overflow=acc["overflow"],
        windows=windows,
        **rely_kw,
    )


def _run_scan_fused(scn: Scenario, key, replicas: int, steps: Optional[int]):
    """Single-scenario fused run on the f64 scan backend."""
    from repro.core import drawplan as dp

    if scn.faults is not None and scn.faults.enabled:
        raise ValueError(
            "draws='fused' does not serve platform faults (the crash "
            "stream is host-staged); use draws='staged'"
        )
    fplan, pvals = dp.lower_scenario(scn)
    n = steps or scn.steps_needed()
    krows = dp.stream_row_keys(key, replicas, fail=fplan.fail)
    prows = {
        s: jnp.tile(jnp.asarray(pvals[s], jnp.float64), (replicas, 1))
        for s in ("arrival", "warm", "cold")
    }
    # fused streams are always gap-based (NHPP thinning is inline), so the
    # prestamped flag the staged NHPP path would set stays off
    scfg = dataclasses.replace(scn.static_config(), prestamped=False)
    acc, t_last = _simulate_batch_fused(
        scfg, fplan, int(n), scn.workload_params(), krows, prows
    )
    return _summarize_scan(scn, jax.tree.map(np.asarray, acc), np.asarray(t_last))


class ServerlessSimulator:
    """Steady-state scale-per-request simulator (paper §3, §4.1).

    >>> sim = ServerlessSimulator(Scenario(...))
    >>> summary = sim.run(jax.random.key(0), replicas=8)
    >>> summary.cold_start_prob

    (Prefer the declarative front door ``repro.core.scenario.run`` — it
    wraps this engine and adds backend/engine selection plus costing.)
    """

    def __init__(self, config: Scenario):
        self.config = config

    @classmethod
    def from_rates(
        cls,
        arrival_rate: float,
        warm_service_time: float,
        cold_service_time: float,
        expiration_threshold: float = 600.0,
        sim_time: float = 1e5,
        **kw,
    ) -> "ServerlessSimulator":
        """Paper-style constructor (exponential processes, Table 1)."""
        cfg = Scenario(
            arrival_process=ExpSimProcess(rate=arrival_rate),
            warm_service_process=ExpSimProcess(rate=1.0 / warm_service_time),
            cold_service_process=ExpSimProcess(rate=1.0 / cold_service_time),
            expiration_threshold=expiration_threshold,
            sim_time=sim_time,
            **kw,
        )
        return cls(cfg)

    def draw_samples(self, key: Array, replicas: int, steps: Optional[int] = None):
        cfg = self.config
        n = steps or cfg.steps_needed()
        return draw_workload_samples(cfg, key, replicas, n)

    def run(
        self,
        key: Array,
        replicas: int = 8,
        steps: Optional[int] = None,
        samples=None,
    ) -> SimulationSummary:
        cfg = self.config
        rel = cfg.reliability
        extras = ()
        if samples is None:
            if rel is not None:
                n = steps or cfg.steps_needed()
                samples, extras = draw_reliability_stream(cfg, key, replicas, n)
            else:
                samples = self.draw_samples(key, replicas, steps)
        elif len(samples) == 2 and isinstance(samples[0], (tuple, list)):
            samples, extras = samples
        elif rel is not None:
            raise ValueError(
                "a reliability run needs the extras drawn alongside the "
                "samples; pass samples=draw_reliability_stream(...) (a "
                "(samples, extras) pair)"
            )
        dts, warms, colds = samples
        extras = tuple(extras)
        flt = cfg.faults
        if flt is not None and flt.crashes:
            # the crash stream rides behind the reliability extras; append
            # it here when the caller staged only the base/rely draws
            n_rely = 0 if rel is None else (1 if rel.retry.max_retries == 0 else 3)
            if len(extras) == n_rely:
                extras = extras + (
                    draw_crash_uniforms(key, replicas, dts.shape[1]),
                )
        acc, t_last = _simulate_batch(
            cfg.static_config(), cfg.workload_params(), dts, warms, colds,
            extras=extras,
        )
        return _summarize_scan(
            cfg, jax.tree.map(np.asarray, acc), np.asarray(t_last)
        )


# ---------------------------------------------------------------------------
# Execution-registry entries (DESIGN.md §9): this module provides the f64
# scan substrate and the steady-state engine.
# ---------------------------------------------------------------------------

register_backend(
    "scan",
    precision="f64",
    kind="native",
    shardable=True,
    description="f64 lax.scan engine (exact; the default substrate)",
)


@register_engine(
    "scan",
    backends=("scan", "pallas", "ref"),
    sweepable=True,
    windowed_backends=("scan", "pallas", "ref"),
    reliability_backends=("scan", "pallas", "ref"),
    fused_backends=("scan", "pallas", "ref"),
    fleet_backends=("scan", "pallas", "ref"),
    faults_backends=("scan", "pallas", "ref"),
    description="steady-state scale-per-request simulator (paper §3/§4.1)",
)
def _scan_engine_run(scn, key, plan, *, replicas, steps, grid, initial_instances):
    del grid, initial_instances  # temporal-engine knobs
    if plan.backend == "scan":
        if plan.resolved_draws == "fused":
            summary = _run_scan_fused(scn, key, replicas, steps)
        else:
            summary = ServerlessSimulator(scn).run(
                key, replicas=replicas, steps=steps
            )
    else:
        from repro.core.scenario import _run_block_single

        summary = _run_block_single(scn, key, replicas, steps, plan)
    return summary, None
