"""ParServerlessSimulator: per-instance concurrency value > 1.

Paper §3.1: "we extended the ServerlessSimulator class to create
ParServerlessSimulator, which simulates serverless platforms that allow
[multiple requests] in the function instances but have a scaling algorithm
similar to scale-per-request platforms" — the Knative / Cloud Run
*concurrency value* pattern (Fig. 1).

Semantics implemented (documented choices):
* Each instance holds up to ``concurrency_value`` in-flight requests,
  processed concurrently; per-request service times are i.i.d. draws
  (processor-sharing slowdown is not modelled — same as the original tool).
* Routing prefers the **newest instance with spare capacity** (consistent
  with the base platform's newest-first policy and Fig. 1's packing).
* A request that finds no spare capacity anywhere triggers a **cold start**
  (new instance) unless the max concurrency level is reached → rejection.
* An instance expires when it has been *fully idle* (no in-flight requests)
  for ``expiration_threshold`` seconds.

State per replica: ``finish[M, c]`` per-request-slot finish times,
``creation[M]``, ``alive[M]``.  The instance-level lifecycle reuses the
closed-form integrals with ``busy_until := max_j finish[:, j]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution import register_engine
from repro.core.scenario import Scenario, StaticConfig, WorkloadParams
from repro.core.reliability import NO_CHILD
from repro.core.simulator import (
    SimulationSummary,
    interval_integrals,
    histogram_update,
    _NEG_INF,
    draw_reliability_stream,
    draw_workload_samples,
)

Array = jax.Array


@dataclasses.dataclass
class ParSimulationSummary(SimulationSummary):
    """Adds the request-level concurrency integral."""

    time_in_flight: Optional[np.ndarray] = None  # ∫ #in-flight-requests dt

    @property
    def avg_in_flight(self) -> float:
        return float(self.time_in_flight.mean() / self.measured_time)

    @property
    def avg_instance_occupancy(self) -> float:
        """Mean in-flight requests per *running* instance-second."""
        return float(
            self.time_in_flight.sum() / np.maximum(self.time_running.sum(), 1e-12)
        )


def _par_scan_fn(cfg: StaticConfig, params: WorkloadParams, concurrency: int):
    t_exp = params.expiration_threshold
    t_end = params.sim_time
    skip = params.skip_time
    max_c = cfg.max_concurrency
    rely = cfg.reliability
    retries = cfg.max_retries > 0

    def step(state, xs):
        (alive, creation, finish, t_prev, acc) = state
        if retries:
            dt, warm_s, cold_s, fail_u, is_first, child_pos, pos = xs
        elif rely:
            dt, warm_s, cold_s, fail_u = xs
        else:
            dt, warm_s, cold_s = xs
        if cfg.prestamped:
            t = dt.astype(jnp.float64)  # absolute-timestamp stream
        else:
            t = t_prev + dt.astype(jnp.float64)
        busy_until = finish.max(axis=1)

        lo = jnp.clip(t_prev, skip, t_end)
        hi = jnp.clip(t, skip, t_end)
        run_t, idle_t = interval_integrals(alive, busy_until, t_exp, lo, hi)
        # request-level in-flight integral: every request slot contributes
        # its overlap with the window (stale finishes clamp to zero).
        in_flight_t = jnp.where(
            alive[:, None], jnp.clip(jnp.minimum(finish, hi) - lo, 0.0, None), 0.0
        ).sum()

        if cfg.track_histogram:
            hist = histogram_update(acc["hist"], alive, busy_until, t_exp, lo, hi)
        else:
            hist = acc["hist"]

        expire_time = busy_until + t_exp
        expired_now = alive & (expire_time <= t)
        lifespan_ok = expired_now & (expire_time > skip) & (expire_time <= t_end)
        lifespan_sum = acc["lifespan_sum"] + jnp.where(
            lifespan_ok, expire_time - creation, 0.0
        ).sum()
        lifespan_count = acc["lifespan_count"] + lifespan_ok.sum()
        alive = alive & ~expired_now

        active = t <= t_end
        if retries:
            # Inert non-first attempts still advance the clock / integrals.
            act = acc["act"]
            active = active & (is_first | act[pos])
        in_flight = (finish > t).sum(axis=1)  # per instance
        has_cap = alive & (in_flight < concurrency)
        any_cap = has_cap.any()
        warm_idx = jnp.argmax(jnp.where(has_cap, creation, _NEG_INF))
        free_mask = ~alive
        any_free = free_mask.any()
        free_idx = jnp.argmax(free_mask)
        n_alive = alive.sum()

        can_cold = (~any_cap) & (n_alive < max_c) & any_free
        overflow = (~any_cap) & (n_alive < max_c) & (~any_free) & active
        is_warm = any_cap & active
        is_cold = can_cold & active
        is_reject = (~any_cap) & (~can_cold) & active

        inst = jnp.where(is_warm, warm_idx, free_idx)
        # choose the first finished request-slot on the chosen instance
        sub_free = finish[inst] <= t
        sub = jnp.argmax(sub_free)
        service = jnp.where(is_warm, warm_s, cold_s).astype(jnp.float64)
        assign = is_warm | is_cold
        if rely:
            # Request slot freed at min(departure, t + t_timeout) — the
            # NO_TIMEOUT sentinel keeps min() the identity.
            occupancy = jnp.minimum(service, params.t_timeout)
        else:
            occupancy = service
        # A cold start repurposes a (possibly stale) slot: wipe it first.
        wiped_row = jnp.where(is_cold, jnp.full((concurrency,), _NEG_INF), finish[inst])
        new_row = wiped_row.at[sub].set(
            jnp.where(assign, t + occupancy, wiped_row[sub])
        )
        finish = finish.at[inst].set(new_row)
        creation = creation.at[inst].set(jnp.where(is_cold, t, creation[inst]))
        alive = alive.at[inst].set(alive[inst] | is_cold)

        counted = t > skip
        if rely:
            timed_out = assign & (service > params.t_timeout)
            failed = (
                assign
                & ~timed_out
                & (fail_u.astype(jnp.float64) < params.p_fail)
            )
            trigger = timed_out | failed | is_reject
            cold_resp = jnp.minimum(cold_s.astype(jnp.float64), params.t_timeout)
            warm_resp = jnp.minimum(warm_s.astype(jnp.float64), params.t_timeout)
        else:
            cold_resp, warm_resp = cold_s, warm_s
        new_acc = dict(
            n_cold=acc["n_cold"] + (is_cold & counted),
            n_warm=acc["n_warm"] + (is_warm & counted),
            n_reject=acc["n_reject"] + (is_reject & counted),
            time_running=acc["time_running"] + run_t,
            time_idle=acc["time_idle"] + idle_t,
            time_in_flight=acc["time_in_flight"] + in_flight_t,
            sum_cold_resp=acc["sum_cold_resp"]
            + jnp.where(is_cold & counted, cold_resp, 0.0),
            sum_warm_resp=acc["sum_warm_resp"]
            + jnp.where(is_warm & counted, warm_resp, 0.0),
            lifespan_sum=lifespan_sum,
            lifespan_count=lifespan_count,
            overflow=acc["overflow"] + overflow,
            hist=hist,
        )
        if rely:
            new_acc["n_timeout"] = acc["n_timeout"] + (timed_out & counted)
            new_acc["n_fail"] = acc["n_fail"] + (failed & counted)
            if retries:
                has_child = child_pos < NO_CHILD
                new_acc["n_retry"] = acc["n_retry"] + (
                    ~is_first & active & counted
                )
                new_acc["n_abandon"] = acc["n_abandon"] + (
                    trigger & ~has_child & counted
                )
                child_c = jnp.minimum(child_pos, act.shape[0] - 1)
                new_acc["act"] = act.at[child_pos].set(
                    act[child_c] | trigger, mode="drop"
                )
            else:
                new_acc["n_retry"] = acc["n_retry"]
                new_acc["n_abandon"] = acc["n_abandon"] + (trigger & counted)
        return (alive, creation, finish, t, new_acc), None

    return step


@functools.partial(jax.jit, static_argnums=(0, 1))
def _simulate_par_batch(
    cfg: StaticConfig, concurrency: int, params: WorkloadParams,
    dts, warms, colds, *extras,
):
    step = _par_scan_fn(cfg, params, concurrency)
    m = cfg.slots

    def one(dt_row, warm_row, cold_row, *ex):
        z = jnp.zeros((), dtype=jnp.float64)
        zi = jnp.zeros((), dtype=jnp.int64)
        acc = dict(
            n_cold=zi,
            n_warm=zi,
            n_reject=zi,
            time_running=z,
            time_idle=z,
            time_in_flight=z,
            sum_cold_resp=z,
            sum_warm_resp=z,
            lifespan_sum=z,
            lifespan_count=zi,
            overflow=zi,
            hist=jnp.zeros((cfg.hist_bins,), dtype=jnp.float64),
        )
        xs = (dt_row, warm_row, cold_row) + tuple(ex)
        if cfg.reliability:
            acc.update(n_timeout=zi, n_fail=zi, n_retry=zi, n_abandon=zi)
        if cfg.max_retries > 0:
            acc["act"] = jnp.zeros(dt_row.shape, dtype=bool)
            xs = xs + (jnp.arange(dt_row.shape[0]),)
        state0 = (
            jnp.zeros((m,), dtype=bool),
            jnp.full((m,), _NEG_INF, dtype=jnp.float64),
            jnp.full((m, concurrency), _NEG_INF, dtype=jnp.float64),
            jnp.zeros((), jnp.float64),
            acc,
        )
        state, _ = jax.lax.scan(step, state0, xs)
        (alive, creation, finish, t_prev, acc) = state
        # tail flush
        busy_until = finish.max(axis=1)
        lo = jnp.clip(t_prev, params.skip_time, params.sim_time)
        hi = jnp.asarray(params.sim_time, dtype=jnp.float64)
        run_t, idle_t = interval_integrals(
            alive, busy_until, params.expiration_threshold, lo, hi
        )
        in_flight_t = jnp.where(
            alive[:, None], jnp.clip(jnp.minimum(finish, hi) - lo, 0.0, None), 0.0
        ).sum()
        acc["time_running"] = acc["time_running"] + run_t
        acc["time_idle"] = acc["time_idle"] + idle_t
        acc["time_in_flight"] = acc["time_in_flight"] + in_flight_t
        if cfg.track_histogram:
            acc["hist"] = histogram_update(
                acc["hist"], alive, busy_until, params.expiration_threshold, lo, hi
            )
        expire_time = busy_until + params.expiration_threshold
        tail_exp = alive & (expire_time <= hi) & (expire_time > params.skip_time)
        acc["lifespan_sum"] = acc["lifespan_sum"] + jnp.where(
            tail_exp, expire_time - creation, 0.0
        ).sum()
        acc["lifespan_count"] = acc["lifespan_count"] + tail_exp.sum()
        acc.pop("act", None)
        return acc, t_prev

    return jax.vmap(one)(dts, warms, colds, *extras)


class ParServerlessSimulator:
    """Concurrency-value platform simulator (Knative / Cloud Run style)."""

    def __init__(self, config: Scenario, concurrency_value: int = 1):
        if concurrency_value < 1:
            raise ValueError("concurrency_value must be >= 1")
        self.config = config
        self.concurrency_value = concurrency_value

    def run(
        self,
        key: Array,
        replicas: int = 8,
        steps: Optional[int] = None,
        samples=None,
    ) -> ParSimulationSummary:
        cfg = self.config
        rel = cfg.reliability
        extras = ()
        if samples is None:
            if rel is not None:
                n = steps or cfg.steps_needed()
                samples, extras = draw_reliability_stream(cfg, key, replicas, n)
            else:
                n = steps or cfg.steps_needed()
                samples = draw_workload_samples(cfg, key, replicas, n)
        elif len(samples) == 2 and isinstance(samples[0], (tuple, list)):
            samples, extras = samples
        elif rel is not None:
            raise ValueError(
                "a reliability run needs the extras drawn alongside the "
                "samples; pass samples=draw_reliability_stream(...) (a "
                "(samples, extras) pair)"
            )
        dts, warms, colds = samples
        acc, t_last = _simulate_par_batch(
            cfg.static_config(),
            self.concurrency_value,
            cfg.workload_params(),
            dts,
            warms,
            colds,
            *extras,
        )
        acc = jax.tree.map(np.asarray, acc)
        t_last = np.asarray(t_last)
        if (t_last < cfg.sim_time).any():
            raise RuntimeError("arrivals ended before sim_time; pass larger steps")
        if acc["overflow"].sum() > 0:
            raise RuntimeError("instance-pool overflow; raise Scenario.slots")
        rely_kw = {}
        if rel is not None:
            rely_kw = dict(
                n_timeout=acc["n_timeout"],
                n_fail=acc["n_fail"],
                n_retry=acc["n_retry"],
                n_abandon=acc["n_abandon"],
            )
        return ParSimulationSummary(
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
            time_in_flight=acc["time_in_flight"],
            **rely_kw,
        )


def _run_block_par(scn, key, plan, replicas, steps):
    """Concurrency-value platform on an f32 block backend: the par row
    launcher drives the ``finish[M, c]`` kernel (``c`` lane-aligned VMEM
    planes; see ``kernels/faas_event_step.py``) from an empty pool.
    Lifespan metrics stay a scan capability (zeros here)."""
    from repro.core.execution import resolve_backend
    from repro.kernels.faas_event_step import PAR_ACC_COLS

    if scn.reliability is not None:
        raise ValueError(
            "the par engine serves reliability on the f64 scan backend "
            "only; use backend='scan'"
        )
    if scn.track_histogram:
        raise ValueError("histograms need the f64 scan backend")
    n = steps or scn.steps_needed()
    dts, warms, colds = draw_workload_samples(scn, key, replicas, n)
    if not scn.prestamped:
        covered = np.asarray(dts, np.float64).sum(axis=1)
        if (covered < scn.sim_time).any():
            raise RuntimeError(
                "pre-drawn arrivals ended before sim_time "
                f"(min final t {covered.min():.1f} < {scn.sim_time}); "
                "pass a larger `steps`"
            )
    rows = lambda v: jnp.full((replicas,), v, jnp.float32)
    launch = resolve_backend(plan.backend).launch_for("par")
    acc = np.asarray(
        launch(
            rows(scn.expiration_threshold),
            rows(scn.sim_time),
            rows(scn.skip_time),
            jnp.asarray(dts, jnp.float32),
            jnp.asarray(warms, jnp.float32),
            jnp.asarray(colds, jnp.float32),
            block_k=plan.resolved_lane_block_k(n),
            max_concurrency=scn.max_concurrency,
            concurrency=scn.concurrency_value,
            slots=scn.slots,
            prestamped=scn.prestamped,
        ),
        np.float64,
    )
    assert acc.shape[1] == PAR_ACC_COLS
    if acc[:, 7].sum() > 0:
        raise RuntimeError("instance-pool overflow; raise Scenario.slots")
    zeros = np.zeros((replicas,))
    return ParSimulationSummary(
        n_cold=acc[:, 0],
        n_warm=acc[:, 1],
        n_reject=acc[:, 2],
        time_running=acc[:, 3],
        time_idle=acc[:, 4],
        sum_cold_resp=acc[:, 5],
        sum_warm_resp=acc[:, 6],
        lifespan_sum=zeros,
        lifespan_count=zeros,
        measured_time=scn.sim_time - scn.skip_time,
        overflow=acc[:, 7],
        time_in_flight=acc[:, 8],
    )


@register_engine(
    "par",
    backends=("scan", "pallas", "ref"),
    reliability_backends=("scan",),
    description="concurrency-value platforms (Knative / Cloud Run pattern)",
)
def _par_engine_run(scn, key, plan, *, replicas, steps, grid, initial_instances):
    del grid, initial_instances  # temporal-engine knobs
    if plan.backend != "scan":
        return _run_block_par(scn, key, plan, replicas, steps), None
    summary = ParServerlessSimulator(scn, scn.concurrency_value).run(
        key, replicas=replicas, steps=steps
    )
    return summary, None
