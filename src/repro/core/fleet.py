"""Multi-function fleet simulation: per-function pools under a shared
cluster-capacity constraint (DESIGN.md §13).

A :class:`FleetScenario` is a tuple of per-function specs (each with its
own arrival process / rate profile, cold/warm service processes,
expiration threshold and per-function concurrency limit) plus shared
cluster parameters: total instance capacity ``n_cluster``, the admission
rule (warm-first, then cold iff both the function limit and the cluster
have headroom) and FIFO queueing with bounded depth ``queue_depth`` when
a function is at its limit or the cluster is full.

Lowering: *function* becomes a second batched axis alongside replicas.
Per-function event streams are staged once, merged into one
time-ordered stream per replica (stable tie-break by function id), and
the merged stream drives the same arrival-driven step as the
single-function engines:

* the f64 scan carries ``[F, slots]`` pools plus ``[F, queue_depth]``
  FIFO queues and a shared occupancy count (``alive.sum()``) gating cold
  starts (:func:`_make_fleet_step` mirrors ``simulator._make_scan_fn``
  op-for-op so a 1-function fleet with ``n_cluster=inf`` is bitwise
  equal to ``Scenario.run``);
* the f32 block kernels map functions onto the rows of one
  ``BLOCK_R``-row block (the shared capacity is a cross-row sum — exact
  in f32 because occupancy counts are small integers), with a
  shared-capacity max-accumulator column in the acc layout
  (``kernels/faas_event_step.py`` / ``kernels/ref.py``, bitwise pair).

:func:`fleet_sweep` rides the one-compile sweep contract: a fleet ×
threshold grid is ONE trace per backend (pinned by
``TRACE_COUNTS["fleet_sweep_*"]``), and ``Execution(devices=...,
shard="grid")`` shards the flattened cell axis on the scan backend.
Combinations the coupling cannot serve (``draws="fused"``, block
backends under ``shard="grid"``, non-``scan`` engines) raise pointed
errors naming a combination that works.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import BillingModel, CostEstimate, estimate_cost
from repro.core.execution import Execution, plan_of, resolve_engine
from repro.core.faults import FaultModel
from repro.core.processes import RateProfile, SimProcess
from repro.core.scenario import GridResult, Scenario, TRACE_COUNTS
from repro.core.simulator import (
    SimulationSummary,
    _NEG_INF,
    draw_crash_uniforms,
    draw_workload_samples,
    fault_interval_integrals,
    interval_integrals,
)

__all__ = [
    "FleetFunction",
    "FleetScenario",
    "FleetSummary",
    "FleetResult",
    "FleetGridResult",
    "fleet_run",
    "fleet_sweep",
]

# Sweepable fleet axes.  All are param-like: every cell shares the one
# staged draw set, so the whole grid is a single trace.
_FLEET_AXES = ("expiration_threshold", "n_cluster", "sim_time", "skip_time")


# --------------------------------------------------------------------------
# Scenario types
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FleetFunction:
    """One function in a fleet: a named single-function workload spec.

    Workload fields mirror :class:`Scenario` (``arrival_process`` or a
    declarative ``rate_profile``/``arrival_rate``, warm/cold service
    processes); platform fields are the per-function expiry threshold
    and concurrency limit.  ``memory_gb`` weights this function's bill
    in the fleet cost roll-up.
    """

    name: str
    arrival_process: Optional[SimProcess] = None
    warm_service_process: Optional[SimProcess] = None
    cold_service_process: Optional[SimProcess] = None
    expiration_threshold: float = 600.0
    max_concurrency: int = 1000
    memory_gb: float = 0.128
    rate_profile: Optional[RateProfile] = None
    arrival_rate: Optional[float] = None

    def __post_init__(self):
        if not isinstance(self.name, str) or not self.name:
            raise ValueError("FleetFunction.name must be a non-empty string")
        if not self.memory_gb > 0:
            raise ValueError(f"memory_gb must be > 0, got {self.memory_gb}")
        # Delegate workload validation + rate_profile/arrival_rate
        # resolution to Scenario, then freeze the resolved process.
        scn = Scenario(
            arrival_process=self.arrival_process,
            warm_service_process=self.warm_service_process,
            cold_service_process=self.cold_service_process,
            expiration_threshold=self.expiration_threshold,
            max_concurrency=self.max_concurrency,
            rate_profile=self.rate_profile,
            arrival_rate=self.arrival_rate,
        )
        object.__setattr__(self, "arrival_process", scn.arrival_process)
        object.__setattr__(self, "rate_profile", None)
        object.__setattr__(self, "arrival_rate", None)

    def as_scenario(
        self, *, sim_time: float, skip_time: float, slots: int
    ) -> Scenario:
        """This function as a standalone single-function Scenario."""
        return Scenario(
            arrival_process=self.arrival_process,
            warm_service_process=self.warm_service_process,
            cold_service_process=self.cold_service_process,
            expiration_threshold=self.expiration_threshold,
            max_concurrency=self.max_concurrency,
            sim_time=float(sim_time),
            skip_time=float(skip_time),
            slots=int(slots),
        )


@dataclasses.dataclass(frozen=True)
class FleetScenario:
    """A set of functions sharing one cluster.

    ``n_cluster`` is the total live-instance capacity across all
    functions (``math.inf`` = uncoupled pools); ``queue_depth`` is the
    per-function FIFO queue used when an arrival cannot start (function
    at its limit or cluster full) — 0 disables queueing (arrivals
    reject, matching the single-function engines).  ``slots`` is the
    per-function instance-pool array size, as in :class:`Scenario`.
    """

    functions: Tuple[FleetFunction, ...]
    n_cluster: float = math.inf
    queue_depth: int = 0
    sim_time: float = 1e5
    skip_time: float = 100.0
    slots: int = 64
    billing: BillingModel = BillingModel()
    faults: Optional[FaultModel] = None

    def __post_init__(self):
        fns = tuple(self.functions)
        object.__setattr__(self, "functions", fns)
        if not fns:
            raise ValueError("FleetScenario needs at least one function")
        if not all(isinstance(f, FleetFunction) for f in fns):
            raise TypeError("FleetScenario.functions must be FleetFunction")
        names = [f.name for f in fns]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate function names in fleet: {names}")
        if not self.n_cluster > 0:
            raise ValueError(f"n_cluster must be > 0, got {self.n_cluster}")
        if self.queue_depth < 0:
            raise ValueError("queue_depth must be >= 0")
        if self.faults is not None and not isinstance(self.faults, FaultModel):
            raise ValueError(
                "FleetScenario.faults must be a FaultModel (or None), got "
                f"{type(self.faults).__name__}"
            )
        if (
            self.faults is not None
            and self.faults.enabled
            and self.queue_depth > 0
        ):
            raise ValueError(
                "platform faults do not serve fleet FIFO queues yet "
                "(eviction would have to reconcile queued work); set "
                "queue_depth=0 or drop the FaultModel"
            )
        if not self.sim_time > 0:
            raise ValueError(f"sim_time must be > 0, got {self.sim_time}")
        if self.skip_time < 0 or self.skip_time >= self.sim_time:
            raise ValueError("need 0 <= skip_time < sim_time")
        if self.slots < 1:
            raise ValueError("slots must be >= 1")

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.functions)

    def with_rates(self, rates: Dict[str, float]) -> "FleetScenario":
        """Copy with the named functions' arrival processes re-rated
        (shape-preserving ``with_rate``; unnamed functions untouched).

        The online fleet service's re-fit hook: each tick it re-levels
        the catalog profiles to the observed per-function rates without
        rebuilding the fleet.  Unknown names raise a pointed KeyError.
        """
        unknown = [n for n in rates if n not in self.names]
        if unknown:
            raise KeyError(
                f"unknown function(s) {unknown}; fleet functions: "
                f"{list(self.names)}"
            )
        fns = []
        for f in self.functions:
            if f.name in rates:
                r = float(rates[f.name])
                if not r > 0:
                    raise ValueError(
                        f"rate for {f.name!r} must be > 0, got {r}"
                    )
                fns.append(
                    dataclasses.replace(
                        f, arrival_process=f.arrival_process.with_rate(r)
                    )
                )
            else:
                fns.append(f)
        return dataclasses.replace(self, functions=tuple(fns))


# --------------------------------------------------------------------------
# Static config / staging
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FleetStatic:
    """Hashable compile-time structure of a fleet cell batch."""

    slots: int
    n_functions: int
    queue_depth: int
    prestamped: bool
    # platform faults (DESIGN.md §15): crash-hazard flag and the
    # capacity-profile step count — the only static legs; rate/edges/
    # values stay traced so fault grids share the one fleet trace
    crashes: bool = False
    cap_steps: int = 0


def _stage_fleet(
    fleet: FleetScenario,
    key,
    replicas: int,
    steps: Optional[int],
    max_sim: float,
) -> Dict[str, np.ndarray]:
    """Draw per-function event streams and merge them per replica.

    Returns host arrays ``times/fids/warms/colds`` of shape ``[R, K]``
    plus the ``prestamped`` flag.  For F > 1 the per-function streams
    are converted to absolute timestamps and stably merged
    (``np.lexsort`` — primary key time, tie-break function id);
    ``prestamped=True``.  For F == 1 the single stream is passed through
    untouched (gap mode for stationary processes) so results are bitwise
    equal to the single-function engines under the same key.
    """
    F = len(fleet.functions)
    per = []
    for f, fn in enumerate(fleet.functions):
        scn_f = fn.as_scenario(
            sim_time=max_sim, skip_time=fleet.skip_time, slots=fleet.slots
        )
        n_f = int(steps) if steps is not None else scn_f.steps_needed()
        key_f = key if F == 1 else jax.random.fold_in(key, f)
        cfg_f = dataclasses.replace(scn_f)
        arr, warms, colds = draw_workload_samples(cfg_f, key_f, replicas, n_f)
        warms = np.asarray(warms, np.float32)
        colds = np.asarray(colds, np.float32)
        if F == 1:
            if scn_f.prestamped:
                times = np.asarray(arr, np.float64)
            else:
                times = np.asarray(arr, np.float32)
                covered = times.astype(np.float64).sum(axis=1)
                if (covered < max_sim).any():
                    raise RuntimeError(
                        f"function {fn.name!r}: pre-drawn arrivals ended "
                        f"before sim_time; pass a larger steps="
                    )
            return dict(
                times=times,
                fids=np.zeros(times.shape, np.int32),
                warms=warms,
                colds=colds,
                prestamped=bool(scn_f.prestamped),
            )
        if scn_f.prestamped:
            times_f = np.asarray(arr, np.float64)
        else:
            times_f = np.cumsum(np.asarray(arr, np.float64), axis=1)
            if (times_f[:, -1] < max_sim).any():
                raise RuntimeError(
                    f"function {fn.name!r}: pre-drawn arrivals ended "
                    f"before sim_time; pass a larger steps="
                )
        fids_f = np.full(times_f.shape, f, np.int32)
        per.append((times_f, fids_f, warms, colds))

    times = np.concatenate([p[0] for p in per], axis=1)
    fids = np.concatenate([p[1] for p in per], axis=1)
    warms = np.concatenate([p[2] for p in per], axis=1)
    colds = np.concatenate([p[3] for p in per], axis=1)
    order = np.lexsort((fids, times))  # stable: time, then function id
    take = lambda a: np.take_along_axis(a, order, axis=1)
    return dict(
        times=take(times),
        fids=take(fids),
        warms=take(warms),
        colds=take(colds),
        prestamped=True,
    )


# --------------------------------------------------------------------------
# f64 scan engine (native backend)
# --------------------------------------------------------------------------


def _fleet_empty_acc(F: int) -> Dict[str, Any]:
    zf = jnp.zeros((F,), jnp.float64)
    zi = jnp.zeros((F,), jnp.int64)
    return dict(
        n_cold=zi,
        n_warm=zi,
        n_reject=zi,
        time_running=zf,
        time_idle=zf,
        sum_cold_resp=zf,
        sum_warm_resp=zf,
        lifespan_sum=zf,
        lifespan_count=zi,
        overflow=zi,
        arrivals=zi,
        enq=zi,
        qserved=zi,
        qwait=zf,
        peak=jnp.zeros((), jnp.float64),
        n_crash=zi,
        n_evict=zi,
        n_interrupt=zi,
    )


def _make_fleet_step(cfg: FleetStatic, p: Dict[str, Any]):
    """Per-arrival step over ``[F, slots]`` pools.

    Mirrors ``simulator._make_scan_fn`` op-for-op on the acting
    function's row (newest-idle routing), with the shared-cluster gate
    ``alive.sum() < n_cluster`` on cold starts and a pre-arrival FIFO
    queue drain (``queue_depth`` iterations, acting function only —
    freed capacity can only serve the head, so in-order drain is
    exact).
    """
    t_exp = p["expiration_threshold"]  # [F]
    limit = p["limit"]  # [F]
    ncl = p["n_cluster"]  # scalar
    t_end = p["sim_time"]
    skip = p["skip_time"]
    Q = cfg.queue_depth
    crashes = cfg.crashes
    capped = cfg.cap_steps > 0
    if Q and (crashes or capped):  # rejected at FleetScenario construction
        raise AssertionError("fleet faults are incompatible with queue_depth > 0")
    integ = jax.vmap(interval_integrals, in_axes=(0, 0, 0, None, None))
    fault_integ = jax.vmap(fault_interval_integrals, in_axes=(0, 0, 0, 0, None, None))

    def step(state, xs):
        if Q:
            alive, creation, busy_until, qt, qw, qc, t_prev, acc = state
        elif crashes:
            alive, creation, busy_until, doom, t_prev, acc = state
        else:
            alive, creation, busy_until, t_prev, acc = state
        if crashes:
            dt, fid, warm_s, cold_s, crash_u = xs
        else:
            dt, fid, warm_s, cold_s = xs
        if cfg.prestamped:
            t = dt.astype(jnp.float64)
        else:
            t = t_prev + dt.astype(jnp.float64)

        lo = jnp.clip(t_prev, skip, t_end)
        hi = jnp.clip(t, skip, t_end)
        if crashes:
            run_t, idle_t = fault_integ(alive, busy_until, t_exp, doom, lo, hi)
        else:
            run_t, idle_t = integ(alive, busy_until, t_exp, lo, hi)

        expire_time = busy_until + t_exp[:, None]
        if crashes:
            # A stamped doom inside the lease ends the instance early; the
            # exit is an expiry otherwise.  Strictly-before keeps the
            # doom == expire tie classified as a normal expiry, matching
            # the single-function scan and both block kernels.
            exit_time = jnp.minimum(expire_time, doom)
            expired_now = alive & (exit_time <= t)
            crash_ok = (
                expired_now
                & (doom < expire_time)
                & (doom > skip)
                & (doom <= t_end)
            )
            n_crash_inc = crash_ok.sum(axis=1)
            lifespan_ok = expired_now & (exit_time > skip) & (exit_time <= t_end)
            lifespan_sum = acc["lifespan_sum"] + jnp.where(
                lifespan_ok, exit_time - creation, 0.0
            ).sum(axis=1)
        else:
            expired_now = alive & (expire_time <= t)
            lifespan_ok = expired_now & (expire_time > skip) & (expire_time <= t_end)
            lifespan_sum = acc["lifespan_sum"] + jnp.where(
                lifespan_ok, expire_time - creation, 0.0
            ).sum(axis=1)
        lifespan_count = acc["lifespan_count"] + lifespan_ok.sum(axis=1)
        alive = alive & ~expired_now

        if capped:
            # Cluster capacity churn: when the profile steps below the
            # current cluster occupancy, shed newest-idle instances
            # fleet-wide (flat index f*M+m breaks creation-time ties,
            # matching the block kernels' lane order).
            cap_now = p["cap_values"][
                jnp.searchsorted(p["cap_edges"], t, side="right")
            ]
            idle_now = alive & (busy_until <= t)
            over = alive.sum().astype(jnp.float64) - cap_now
            crf = creation.reshape(-1)
            idf = idle_now.reshape(-1)
            ids = jnp.arange(crf.shape[0])
            newer = (crf[None, :] > crf[:, None]) | (
                (crf[None, :] == crf[:, None]) & (ids[None, :] < ids[:, None])
            )
            rank = (idf[None, :] & newer).sum(axis=1)
            evict = (
                idf & (rank.astype(jnp.float64) < over) & (t <= t_end)
            ).reshape(alive.shape)
            evict_ok = evict & (t > skip)
            lifespan_sum = lifespan_sum + jnp.where(
                evict_ok, t - creation, 0.0
            ).sum(axis=1)
            lifespan_count = lifespan_count + evict_ok.sum(axis=1)
            n_evict_inc = evict_ok.sum(axis=1)
            alive = alive & ~evict

        active = t <= t_end
        counted = t > skip
        acc = dict(
            acc,
            time_running=acc["time_running"] + run_t,
            time_idle=acc["time_idle"] + idle_t,
            lifespan_sum=lifespan_sum,
            lifespan_count=lifespan_count,
        )
        if crashes:
            acc = dict(acc, n_crash=acc["n_crash"] + n_crash_inc)
        if capped:
            acc = dict(acc, n_evict=acc["n_evict"] + n_evict_inc)

        if Q:
            # FIFO drain: freed capacity serves queued requests of the
            # acting function before its new arrival is routed.
            def drain(_, dstate):
                alive, creation, busy_until, qt, qw, qc, acc = dstate
                ht = qt[fid, 0]
                hw = qw[fid, 0]
                hc = qc[fid, 0]
                has = (ht > _NEG_INF * 0.5) & active
                idle_f = alive[fid] & (busy_until[fid] <= t)
                any_idle_f = idle_f.any()
                warm_idx_f = jnp.argmax(jnp.where(idle_f, creation[fid], _NEG_INF))
                free_f = ~alive[fid]
                any_free_f = free_f.any()
                free_idx_f = jnp.argmax(free_f)
                n_alive_f = alive[fid].sum()
                cluster = alive.sum()
                can_warm = has & any_idle_f
                can_cold = (
                    has
                    & (~any_idle_f)
                    & (n_alive_f < limit[fid])
                    & any_free_f
                    & (cluster < ncl)
                )
                serve = can_warm | can_cold
                chosen = jnp.where(can_warm, warm_idx_f, free_idx_f)
                service = jnp.where(can_warm, hw, hc)
                new_busy = jnp.where(serve, t + service, busy_until[fid, chosen])
                busy_until = busy_until.at[fid, chosen].set(new_busy)
                new_creation = jnp.where(can_cold, t, creation[fid, chosen])
                creation = creation.at[fid, chosen].set(new_creation)
                alive = alive.at[fid, chosen].set(alive[fid, chosen] | can_cold)
                acc = dict(
                    acc,
                    n_cold=acc["n_cold"].at[fid].add(can_cold & counted),
                    n_warm=acc["n_warm"].at[fid].add(can_warm & counted),
                    sum_cold_resp=acc["sum_cold_resp"]
                    .at[fid]
                    .add(jnp.where(can_cold & counted, hc, 0.0)),
                    sum_warm_resp=acc["sum_warm_resp"]
                    .at[fid]
                    .add(jnp.where(can_warm & counted, hw, 0.0)),
                    qserved=acc["qserved"].at[fid].add(serve & counted),
                    qwait=acc["qwait"]
                    .at[fid]
                    .add(jnp.where(serve & counted, t - ht, 0.0)),
                )
                tail = jnp.full((1,), _NEG_INF)
                shift = lambda qx: qx.at[fid].set(
                    jnp.where(serve, jnp.concatenate([qx[fid, 1:], tail]), qx[fid])
                )
                return alive, creation, busy_until, shift(qt), shift(qw), shift(qc), acc

            alive, creation, busy_until, qt, qw, qc, acc = jax.lax.fori_loop(
                0, Q, drain, (alive, creation, busy_until, qt, qw, qc, acc)
            )

        # Arrival routing for the acting function.
        idle_mask = alive & (busy_until <= t)
        any_idle = idle_mask.any(axis=1)
        warm_idx = jnp.argmax(jnp.where(idle_mask, creation, _NEG_INF), axis=1)
        free_mask = ~alive
        any_free = free_mask.any(axis=1)
        free_idx = jnp.argmax(free_mask, axis=1)
        n_alive = alive.sum(axis=1)
        cluster = alive.sum()

        any_idle_f = any_idle[fid]
        can_cold_f = (
            (~any_idle_f)
            & (n_alive[fid] < limit[fid])
            & any_free[fid]
            & (cluster < ncl)
        )
        if capped:
            # admission gate while degraded: no cold start over the ceiling
            can_cold_f = can_cold_f & (cluster.astype(jnp.float64) < cap_now)
        overflow_f = (
            (~any_idle_f) & (n_alive[fid] < limit[fid]) & (~any_free[fid]) & active
        )
        is_warm = any_idle_f & active
        is_cold = can_cold_f & active
        if Q:
            qlen_f = (qt[fid] > _NEG_INF * 0.5).sum()
            can_enq = (~any_idle_f) & (~can_cold_f) & (qlen_f < Q)
            is_enq = can_enq & active
            is_reject = (~any_idle_f) & (~can_cold_f) & (~can_enq) & active
        else:
            is_reject = (~any_idle_f) & (~can_cold_f) & active

        chosen = jnp.where(is_warm, warm_idx[fid], free_idx[fid])
        service = jnp.where(is_warm, warm_s, cold_s).astype(jnp.float64)
        assign = is_warm | is_cold
        new_busy = jnp.where(assign, t + service, busy_until[fid, chosen])
        busy_until = busy_until.at[fid, chosen].set(new_busy)
        new_creation = jnp.where(is_cold, t, creation[fid, chosen])
        creation = creation.at[fid, chosen].set(new_creation)
        alive = alive.at[fid, chosen].set(alive[fid, chosen] | is_cold)
        if crashes:
            # A cold start draws the instance's Exp(crash_rate) lifetime
            # from its pre-drawn uniform (memoryless hazard); warm serves
            # keep the instance's existing doom.  The fleet has no
            # reliability layer, so an interrupted attempt is just one the
            # serving instance does not survive.
            life = -jnp.log(1.0 - crash_u.astype(jnp.float64)) / p["crash_rate"]
            doom_chosen = jnp.where(is_cold, t + life, doom[fid, chosen])
            doom = doom.at[fid, chosen].set(doom_chosen)
            interrupted = assign & (doom_chosen < t + service)
        if Q:
            pos = jnp.minimum(qlen_f, Q - 1)
            qt = qt.at[fid, pos].set(jnp.where(is_enq, t, qt[fid, pos]))
            qw = qw.at[fid, pos].set(jnp.where(is_enq, warm_s, qw[fid, pos]))
            qc = qc.at[fid, pos].set(jnp.where(is_enq, cold_s, qc[fid, pos]))

        acc = dict(
            acc,
            n_cold=acc["n_cold"].at[fid].add(is_cold & counted),
            n_warm=acc["n_warm"].at[fid].add(is_warm & counted),
            n_reject=acc["n_reject"].at[fid].add(is_reject & counted),
            sum_cold_resp=acc["sum_cold_resp"]
            .at[fid]
            .add(jnp.where(is_cold & counted, cold_s, 0.0)),
            sum_warm_resp=acc["sum_warm_resp"]
            .at[fid]
            .add(jnp.where(is_warm & counted, warm_s, 0.0)),
            overflow=acc["overflow"].at[fid].add(overflow_f),
            arrivals=acc["arrivals"].at[fid].add(active & counted),
            peak=jnp.maximum(acc["peak"], alive.sum().astype(jnp.float64)),
        )
        if crashes:
            acc = dict(
                acc,
                n_interrupt=acc["n_interrupt"].at[fid].add(interrupted & counted),
            )
        if Q:
            acc = dict(acc, enq=acc["enq"].at[fid].add(is_enq & counted))
            return (alive, creation, busy_until, qt, qw, qc, t, acc), None
        if crashes:
            return (alive, creation, busy_until, doom, t, acc), None
        return (alive, creation, busy_until, t, acc), None

    return step


def _fleet_flush(cfg: FleetStatic, p: Dict[str, Any], state):
    """Integrate the tail (last arrival → sim_time); mirrors ``_flush``."""
    Q = cfg.queue_depth
    if Q:
        alive, creation, busy_until, qt, _, _, t_prev, acc = state
    elif cfg.crashes:
        alive, creation, busy_until, doom, t_prev, acc = state
    else:
        alive, creation, busy_until, t_prev, acc = state
    t_exp = p["expiration_threshold"]
    t_end = p["sim_time"]
    skip = p["skip_time"]
    lo = jnp.clip(t_prev, skip, t_end)
    hi = jnp.asarray(t_end, jnp.float64)
    if cfg.crashes:
        fault_integ = jax.vmap(
            fault_interval_integrals, in_axes=(0, 0, 0, 0, None, None)
        )
        run_t, idle_t = fault_integ(alive, busy_until, t_exp, doom, lo, hi)
        expire_time = busy_until + t_exp[:, None]
        exit_time = jnp.minimum(expire_time, doom)
        tail_exp = alive & (exit_time <= hi) & (exit_time > skip)
        acc = dict(
            acc,
            n_crash=acc["n_crash"] + (tail_exp & (doom < expire_time)).sum(axis=1),
        )
    else:
        integ = jax.vmap(interval_integrals, in_axes=(0, 0, 0, None, None))
        run_t, idle_t = integ(alive, busy_until, t_exp, lo, hi)
        exit_time = busy_until + t_exp[:, None]
        tail_exp = alive & (exit_time <= hi) & (exit_time > skip)
    acc = dict(
        acc,
        time_running=acc["time_running"] + run_t,
        time_idle=acc["time_idle"] + idle_t,
        lifespan_sum=acc["lifespan_sum"]
        + jnp.where(tail_exp, exit_time - creation, 0.0).sum(axis=1),
        lifespan_count=acc["lifespan_count"] + tail_exp.sum(axis=1),
        qleft=(
            (qt > _NEG_INF * 0.5).sum(axis=1)
            if Q
            else jnp.zeros((cfg.n_functions,), jnp.int64)
        ),
        t_last=t_prev,
    )
    return acc


def _fleet_scan_one(cfg: FleetStatic, p, dt_row, fid_row, warm_row, cold_row):
    F, M, Q = cfg.n_functions, cfg.slots, cfg.queue_depth
    step = _make_fleet_step(cfg, p)
    alive0 = jnp.zeros((F, M), bool)
    neg = jnp.full((F, M), _NEG_INF, jnp.float64)
    acc = _fleet_empty_acc(F)
    if Q:
        qneg = jnp.full((F, Q), _NEG_INF, jnp.float64)
        state0 = (alive0, neg, neg, qneg, qneg, qneg, jnp.zeros((), jnp.float64), acc)
    elif cfg.crashes:
        doom0 = jnp.full((F, M), jnp.inf, jnp.float64)
        state0 = (alive0, neg, neg, doom0, jnp.zeros((), jnp.float64), acc)
    else:
        state0 = (alive0, neg, neg, jnp.zeros((), jnp.float64), acc)
    xs = (dt_row, fid_row, warm_row, cold_row)
    if cfg.crashes:
        xs = xs + (p["crash_u"],)
    state, _ = jax.lax.scan(step, state0, xs)
    return _fleet_flush(cfg, p, state)


def _fleet_rows(cfg, params, times, fids, warms, colds):
    def one(p, dt_row, fid_row, warm_row, cold_row):
        return _fleet_scan_one(cfg, p, dt_row, fid_row, warm_row, cold_row)

    return jax.vmap(one)(params, times, fids, warms, colds)


@functools.partial(jax.jit, static_argnums=(0,))
def _fleet_simulate_sweep(cfg, params, times, fids, warms, colds):
    TRACE_COUNTS["fleet_sweep_scan"] += 1
    return _fleet_rows(cfg, params, times, fids, warms, colds)


@functools.lru_cache(maxsize=None)
def fleet_sweep_executable(mesh=None):
    """jit-compiled fleet batch runner; shard_map over cells when given
    a 1-D ``("grid",)`` mesh (same layout contract as
    ``simulator.sweep_executable``)."""
    if mesh is None:
        return _fleet_simulate_sweep

    from jax.sharding import PartitionSpec

    spec = PartitionSpec("grid")

    def fn(cfg, params, times, fids, warms, colds):
        TRACE_COUNTS["fleet_sweep_sharded"] += 1
        # check_vma=False: rows are independent and the body has no
        # collectives; the scan's constant initial carry would otherwise
        # fail the varying-axes type check against its varying output
        return jax.shard_map(
            functools.partial(_fleet_rows, cfg),
            mesh=mesh,
            in_specs=(spec,) * 5,
            out_specs=spec,
            check_vma=False,
        )(params, times, fids, warms, colds)

    return jax.jit(fn, static_argnums=(0,))


# --------------------------------------------------------------------------
# Execution resolution
# --------------------------------------------------------------------------


def _fleet_capable_combos() -> List[str]:
    out = []
    for ename in ("scan", "temporal", "par"):
        try:
            espec = resolve_engine(ename)
        except ValueError:
            continue
        for bname in espec.fleet_backends:
            out.append(f"engine='{ename}', backend='{bname}'")
    return sorted(out)


def _resolve_fleet(execution, engine, backend):
    plan = plan_of(execution, engine, backend)
    espec, bspec = plan.resolve()
    if bspec.name not in espec.fleet_backends:
        combos = "; ".join(_fleet_capable_combos()) or "<none registered>"
        raise ValueError(
            f"engine '{espec.name}' does not serve the fleet coupling on "
            f"backend '{bspec.name}' (shared cluster capacity + per-function "
            f"pools); fleet-capable combinations: {combos}"
        )
    if plan.resolved_draws == "fused":
        raise ValueError(
            "fleet simulations stage their merged per-function event streams "
            "on the host; draws='fused' is not served — use draws='staged' "
            "(the default), which works with backend='scan', 'pallas' and 'ref'"
        )
    if plan.shard == "grid" and bspec.kind != "native":
        raise ValueError(
            "fleet shard='grid' is served by the f64 scan backend only "
            "(block backends fold functions into the row-block layout); "
            "use Execution(devices=..., shard='grid', backend='scan'), or "
            "drop shard= to keep backend='pallas'/'ref'"
        )
    return plan, espec, bspec


# --------------------------------------------------------------------------
# Cell batch construction + launch
# --------------------------------------------------------------------------


def _normalize_thr(fleet: FleetScenario, v) -> Tuple[float, ...]:
    F = len(fleet.functions)
    if np.ndim(v) == 0:
        out = (float(v),) * F
    else:
        out = tuple(float(x) for x in v)
        if len(out) != F:
            raise ValueError(
                f"expiration_threshold axis values must be scalars or "
                f"length-{F} sequences, got length {len(out)}"
            )
    if not all(x > 0 for x in out):
        raise ValueError(f"expiration_threshold must be > 0, got {v!r}")
    return out


def _cell_params(fleet: FleetScenario, names, combo):
    d = dict(zip(names, combo))
    thr = d.get(
        "expiration_threshold",
        tuple(f.expiration_threshold for f in fleet.functions),
    )
    thr = _normalize_thr(fleet, thr)
    ncl = float(d.get("n_cluster", fleet.n_cluster))
    sim = float(d.get("sim_time", fleet.sim_time))
    skip = float(d.get("skip_time", fleet.skip_time))
    if not ncl > 0:
        raise ValueError(f"n_cluster must be > 0, got {ncl}")
    if not sim > 0 or skip < 0 or skip >= sim:
        raise ValueError(f"need 0 <= skip_time < sim_time, got {skip}, {sim}")
    return thr, ncl, sim, skip


def _launch_fleet_cells(
    fleet: FleetScenario,
    staged: Dict[str, np.ndarray],
    cells: Dict[str, np.ndarray],
    plan,
    bspec,
    replicas: int,
) -> List[Dict[str, Any]]:
    """Run every (cell, replica) fleet row; one device call per backend.

    Returns one dict per cell: per-function ``summaries`` (vector
    :class:`SimulationSummary` over replicas) plus fleet arrays
    ``arrivals/enq/qserved/qwait/qleft`` (``[F, R]``) and ``peak``
    (``[R]``).
    """
    if bspec.kind == "native":
        return _scan_fleet_cells(fleet, staged, cells, plan, replicas)
    return _block_fleet_cells(fleet, staged, cells, plan, bspec, replicas)


def _scan_fleet_cells(fleet, staged, cells, plan, replicas):
    F = len(fleet.functions)
    R = replicas
    n_cells = len(cells["n_cluster"])
    C = n_cells * R

    rep_rows = lambda a: np.repeat(a, R, axis=0)
    params = dict(
        expiration_threshold=jnp.asarray(
            rep_rows(cells["expiration_threshold"]), jnp.float64
        ),
        limit=jnp.asarray(rep_rows(cells["limit"]), jnp.float64),
        n_cluster=jnp.asarray(np.repeat(cells["n_cluster"], R), jnp.float64),
        sim_time=jnp.asarray(np.repeat(cells["sim_time"], R), jnp.float64),
        skip_time=jnp.asarray(np.repeat(cells["skip_time"], R), jnp.float64),
    )
    flt = fleet.faults if fleet.faults is not None and fleet.faults.enabled else None
    if flt is not None and flt.crashes:
        params["crash_rate"] = jnp.full((C,), flt.crash_rate, jnp.float64)
        params["crash_u"] = jnp.asarray(
            np.tile(staged["crash_u"], (n_cells, 1)), jnp.float64
        )
    if flt is not None and flt.cap_steps:
        params["cap_edges"] = jnp.asarray(
            np.tile(np.asarray(flt.capacity.edges, np.float64), (C, 1))
        )
        params["cap_values"] = jnp.asarray(
            np.tile(np.asarray(flt.capacity.values, np.float64), (C, 1))
        )
    times = jnp.asarray(np.tile(staged["times"], (n_cells, 1)))
    fids = jnp.asarray(np.tile(staged["fids"], (n_cells, 1)))
    warms = jnp.asarray(np.tile(staged["warms"], (n_cells, 1)))
    colds = jnp.asarray(np.tile(staged["colds"], (n_cells, 1)))

    cfg = FleetStatic(
        slots=fleet.slots,
        n_functions=F,
        queue_depth=fleet.queue_depth,
        prestamped=staged["prestamped"],
        crashes=bool(flt is not None and flt.crashes),
        cap_steps=flt.cap_steps if flt is not None else 0,
    )

    mesh = plan.mesh() if plan.shard == "grid" else None
    if mesh is not None:
        n_dev = mesh.devices.size
        pad = (-C) % n_dev
        if pad:
            pad_rows = lambda a: jnp.concatenate(
                [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])], axis=0
            )
            params = jax.tree_util.tree_map(pad_rows, params)
            times, fids, warms, colds = map(pad_rows, (times, fids, warms, colds))
    fn = fleet_sweep_executable(mesh=mesh)
    acc = fn(cfg, params, times, fids, warms, colds)
    acc = {k: np.asarray(v)[:C] for k, v in acc.items()}

    if not staged["prestamped"]:
        short = acc["t_last"] < np.repeat(cells["sim_time"], R)
        if short.any():
            raise RuntimeError(
                "pre-drawn arrivals ended before sim_time; pass a larger steps="
            )
    if acc["overflow"].sum() > 0:
        raise RuntimeError(
            "instance-pool overflow during fleet run; raise FleetScenario.slots"
        )

    out = []
    per_f = lambda k, c: acc[k].reshape(n_cells, R, F)[c]  # [R, F]
    for c in range(n_cells):
        measured = float(cells["sim_time"][c] - cells["skip_time"][c])
        summaries = [
            SimulationSummary(
                n_cold=per_f("n_cold", c)[:, f],
                n_warm=per_f("n_warm", c)[:, f],
                n_reject=per_f("n_reject", c)[:, f],
                time_running=per_f("time_running", c)[:, f],
                time_idle=per_f("time_idle", c)[:, f],
                sum_cold_resp=per_f("sum_cold_resp", c)[:, f],
                sum_warm_resp=per_f("sum_warm_resp", c)[:, f],
                lifespan_sum=per_f("lifespan_sum", c)[:, f],
                lifespan_count=per_f("lifespan_count", c)[:, f],
                measured_time=measured,
                overflow=per_f("overflow", c)[:, f],
                **(
                    dict(
                        n_crash=per_f("n_crash", c)[:, f],
                        n_evict=per_f("n_evict", c)[:, f],
                        n_interrupt=per_f("n_interrupt", c)[:, f],
                    )
                    if flt is not None
                    else {}
                ),
            )
            for f in range(F)
        ]
        out.append(
            dict(
                summaries=summaries,
                arrivals=per_f("arrivals", c).T,
                enq=per_f("enq", c).T,
                qserved=per_f("qserved", c).T,
                qwait=per_f("qwait", c).T,
                qleft=per_f("qleft", c).T,
                peak=acc["peak"].reshape(n_cells, R)[c],
            )
        )
    return out


def _block_fleet_cells(fleet, staged, cells, plan, bspec, replicas):
    from repro.kernels.faas_event_step import BLOCK_R, FLEET_ACC_COLS

    F = len(fleet.functions)
    if F > BLOCK_R:
        raise ValueError(
            f"block backends serve fleets of at most {BLOCK_R} functions "
            f"(functions ride the {BLOCK_R}-row block of the f32 kernels); "
            f"got F={F} — use backend='scan'"
        )
    R = replicas
    n_cells = len(cells["n_cluster"])
    rows = n_cells * R * BLOCK_R
    K = staged["times"].shape[1]
    pad_f = BLOCK_R - F

    def per_fn_rows(a, fill):
        # [n_cells, F] -> [rows]: function f of cell c, replica r sits at
        # row ((c*R + r)*BLOCK_R + f); padded functions are inert.
        if pad_f:
            a = np.concatenate([a, np.full((n_cells, pad_f), fill)], axis=1)
        return np.repeat(a, R, axis=0).reshape(rows).astype(np.float32)

    per_cell_rows = lambda a: np.repeat(
        np.asarray(a, np.float64), R * BLOCK_R
    ).astype(np.float32)
    ncl = np.where(
        np.isfinite(cells["n_cluster"]), cells["n_cluster"], 1e30
    )

    tile8 = lambda a, dt: np.repeat(
        np.tile(np.asarray(a, dt), (n_cells, 1)), BLOCK_R, axis=0
    )
    flt = fleet.faults if fleet.faults is not None and fleet.faults.enabled else None
    fault_kw = {}
    if flt is not None and flt.crashes:
        fault_kw["crash_rate"] = per_cell_rows(np.full(n_cells, flt.crash_rate))
        fault_kw["crash_u"] = tile8(staged["crash_u"], np.float32)
    if flt is not None and flt.cap_steps:
        fault_kw["cap_edges"] = np.tile(
            np.asarray(flt.capacity.edges, np.float32), (rows, 1)
        )
        fault_kw["cap_values"] = np.tile(
            np.asarray(flt.capacity.values, np.float32), (rows, 1)
        )
    launch = bspec.launch_for("fleet")
    acc, qleft = launch(
        per_fn_rows(cells["expiration_threshold"], 1.0),
        per_fn_rows(cells["limit"], 0.0),
        per_cell_rows(ncl),
        per_cell_rows(cells["sim_time"]),
        per_cell_rows(cells["skip_time"]),
        tile8(staged["times"], np.float32),
        tile8(staged["fids"], np.float32),
        tile8(staged["warms"], np.float32),
        tile8(staged["colds"], np.float32),
        slots=fleet.slots,
        queue_depth=fleet.queue_depth,
        prestamped=staged["prestamped"],
        block_k=plan.resolved_lane_block_k(K),
        **fault_kw,
    )
    acc_cols = FLEET_ACC_COLS + (3 if flt is not None else 0)
    acc = np.asarray(acc).reshape(n_cells, R, BLOCK_R, acc_cols)
    qleft = np.asarray(qleft).reshape(n_cells, R, BLOCK_R)
    if acc[:, :, :, 7].sum() > 0:
        raise RuntimeError(
            "instance-pool overflow during fleet run; raise FleetScenario.slots"
        )

    out = []
    for c in range(n_cells):
        measured = float(cells["sim_time"][c] - cells["skip_time"][c])
        a = acc[c]  # [R, BLOCK_R, cols]
        zeros = np.zeros((R,))
        summaries = [
            SimulationSummary(
                n_cold=a[:, f, 0],
                n_warm=a[:, f, 1],
                n_reject=a[:, f, 2],
                time_running=a[:, f, 3],
                time_idle=a[:, f, 4],
                sum_cold_resp=a[:, f, 5],
                sum_warm_resp=a[:, f, 6],
                lifespan_sum=zeros,
                lifespan_count=zeros,
                measured_time=measured,
                overflow=a[:, f, 7],
                **(
                    dict(
                        n_crash=a[:, f, FLEET_ACC_COLS + 0],
                        n_evict=a[:, f, FLEET_ACC_COLS + 1],
                        n_interrupt=a[:, f, FLEET_ACC_COLS + 2],
                    )
                    if flt is not None
                    else {}
                ),
            )
            for f in range(F)
        ]
        out.append(
            dict(
                summaries=summaries,
                arrivals=a[:, :F, 8].T,
                enq=a[:, :F, 9].T,
                qserved=a[:, :F, 10].T,
                qwait=a[:, :F, 11].T,
                qleft=qleft[c][:, :F].T,
                peak=a[:, 0, 12],
            )
        )
    return out


# --------------------------------------------------------------------------
# Results
# --------------------------------------------------------------------------


@dataclasses.dataclass
class FleetSummary:
    """Per-function + fleet-aggregate outcome of one fleet cell.

    ``summaries[f]`` is the familiar vector :class:`SimulationSummary`
    for function f (over replicas); the fleet arrays are ``[F, R]``
    counters (``arrivals``, ``enqueued``, ``queue_served``,
    ``queue_wait_sum``, ``queue_left``) plus the per-replica cluster
    occupancy ``peak_cluster``.
    """

    functions: Tuple[str, ...]
    summaries: List[SimulationSummary]
    arrivals: np.ndarray
    enqueued: np.ndarray
    queue_served: np.ndarray
    queue_wait_sum: np.ndarray
    queue_left: np.ndarray
    peak_cluster: np.ndarray
    n_cluster: float
    measured_time: float

    def __getitem__(self, name: str) -> SimulationSummary:
        return self.summaries[self.functions.index(name)]

    @property
    def cold_start_prob(self) -> np.ndarray:
        """Per-function cold-start probability, ``[F]``."""
        return np.array([s.cold_start_prob for s in self.summaries])

    @property
    def avg_response_time(self) -> np.ndarray:
        return np.array([s.avg_response_time for s in self.summaries])

    @property
    def rejection_prob(self) -> np.ndarray:
        return np.array([s.rejection_prob for s in self.summaries])

    @property
    def queue_wait_avg(self) -> np.ndarray:
        """Mean queue wait per queue-served request, per function ``[F]``."""
        served = self.queue_served.sum(axis=1)
        return self.queue_wait_sum.sum(axis=1) / np.maximum(served, 1)

    @property
    def avg_cluster_occupancy(self) -> float:
        """Mean live instances across the cluster (all functions)."""
        return float(sum(s.avg_server_count for s in self.summaries))

    @property
    def cluster_utilization(self) -> float:
        """Mean occupancy / ``n_cluster`` (0.0 for an unbounded cluster)."""
        if not math.isfinite(self.n_cluster):
            return 0.0
        return self.avg_cluster_occupancy / self.n_cluster

    @property
    def max_peak_cluster(self) -> float:
        return float(np.max(self.peak_cluster))

    def to_dict(self) -> Dict[str, Any]:
        return dict(
            functions=list(self.functions),
            cold_start_prob=self.cold_start_prob.tolist(),
            rejection_prob=self.rejection_prob.tolist(),
            avg_response_time=self.avg_response_time.tolist(),
            queue_wait_avg=self.queue_wait_avg.tolist(),
            avg_cluster_occupancy=self.avg_cluster_occupancy,
            cluster_utilization=self.cluster_utilization,
            max_peak_cluster=self.max_peak_cluster,
            n_cluster=(
                self.n_cluster if math.isfinite(self.n_cluster) else "inf"
            ),
        )


@dataclasses.dataclass
class FleetResult:
    """A fleet run: the scenario, its summary and per-function costs."""

    fleet: FleetScenario
    summary: FleetSummary
    costs: List[CostEstimate]

    def cost_of(self, name: str) -> CostEstimate:
        return self.costs[self.fleet.names.index(name)]

    @property
    def developer_cost(self) -> float:
        """Fleet-total developer bill (all functions)."""
        return float(sum(c.developer_total for c in self.costs))

    @property
    def provider_cost(self) -> float:
        """Fleet-total provider infrastructure cost."""
        return float(sum(c.provider_infra_cost for c in self.costs))


@dataclasses.dataclass
class FleetGridResult(GridResult):
    """A :class:`GridResult` whose trailing named axis is ``function``.

    ``sel(function="thumbnail")`` selects by catalog name (or by
    positional index); the per-function metric grids are joined by the
    fleet-level ``queue_wait_avg``, ``cluster_utilization`` and
    ``peak_cluster`` grids (cluster-level values broadcast over the
    function axis).
    """

    queue_wait_avg: Optional[np.ndarray] = None
    cluster_utilization: Optional[np.ndarray] = None
    peak_cluster: Optional[np.ndarray] = None

    _METRIC_FIELDS = GridResult._METRIC_FIELDS + (
        "queue_wait_avg",
        "cluster_utilization",
        "peak_cluster",
    )


# --------------------------------------------------------------------------
# Front door
# --------------------------------------------------------------------------


def _validate_axes(fleet: FleetScenario, over: Dict[str, Sequence]) -> None:
    for name in over:
        if name in ("queue_depth", "functions", "slots"):
            raise ValueError(
                f"'{name}' is compile-time fleet structure, not a sweepable "
                f"axis; build separate FleetScenarios instead "
                f"(sweepable: {', '.join(_FLEET_AXES)})"
            )
        if name not in _FLEET_AXES:
            raise ValueError(
                f"unknown fleet sweep axis '{name}'; sweepable axes: "
                f"{', '.join(_FLEET_AXES)}"
            )
        if len(list(over[name])) == 0:
            raise ValueError(f"sweep axis '{name}' must be non-empty")


def _fleet_cells(fleet, over, key, replicas, plan, bspec, steps):
    names = list(over)
    axis_vals = {n: tuple(over[n]) for n in names}
    combos = list(itertools.product(*[axis_vals[n] for n in names]))
    if not combos:
        combos = [()]
    F = len(fleet.functions)
    per_cell = [_cell_params(fleet, names, c) for c in combos]
    max_sim = max(p[2] for p in per_cell)
    staged = _stage_fleet(fleet, key, replicas, steps, max_sim)
    if fleet.faults is not None and fleet.faults.enabled and fleet.faults.crashes:
        # One crash uniform per merged event, positional — drawn after the
        # per-function streams are merged so the stream stays one [R, K]
        # plane regardless of F (fold_in-salted; see CRASH_SALT).
        staged["crash_u"] = np.asarray(
            draw_crash_uniforms(key, replicas, staged["times"].shape[1]),
            np.float32,
        )
    cells = dict(
        expiration_threshold=np.array([p[0] for p in per_cell], np.float64),
        limit=np.broadcast_to(
            np.array([f.max_concurrency for f in fleet.functions], np.float64),
            (len(per_cell), F),
        ),
        n_cluster=np.array([p[1] for p in per_cell], np.float64),
        sim_time=np.array([p[2] for p in per_cell], np.float64),
        skip_time=np.array([p[3] for p in per_cell], np.float64),
    )
    cell_outs = _launch_fleet_cells(fleet, staged, cells, plan, bspec, replicas)
    return axis_vals, cells, cell_outs


def _fleet_summary(fleet, cells, cell_out, c) -> FleetSummary:
    return FleetSummary(
        functions=fleet.names,
        summaries=cell_out["summaries"],
        arrivals=cell_out["arrivals"],
        enqueued=cell_out["enq"],
        queue_served=cell_out["qserved"],
        queue_wait_sum=cell_out["qwait"],
        queue_left=cell_out["qleft"],
        peak_cluster=cell_out["peak"],
        n_cluster=float(cells["n_cluster"][c]),
        measured_time=float(cells["sim_time"][c] - cells["skip_time"][c]),
    )


def _fleet_costs(fleet: FleetScenario, summaries) -> List[CostEstimate]:
    return [
        estimate_cost(
            s, dataclasses.replace(fleet.billing, memory_gb=fn.memory_gb)
        )
        for fn, s in zip(fleet.functions, summaries)
    ]


def fleet_run(
    fleet: FleetScenario,
    key,
    *,
    replicas: int = 4,
    engine: Optional[str] = None,
    backend: Optional[str] = None,
    execution: Optional[Execution] = None,
    steps: Optional[int] = None,
) -> FleetResult:
    """Run one fleet cell; returns per-function + aggregate metrics."""
    plan, _, bspec = _resolve_fleet(execution, engine, backend)
    if plan.shard is not None:
        raise ValueError(
            "shard= applies to fleet_sweep(); fleet_run executes one cell"
        )
    _, cells, outs = _fleet_cells(fleet, {}, key, replicas, plan, bspec, steps)
    summary = _fleet_summary(fleet, cells, outs[0], 0)
    return FleetResult(
        fleet=fleet,
        summary=summary,
        costs=_fleet_costs(fleet, summary.summaries),
    )


def fleet_sweep(
    fleet: FleetScenario,
    over: Dict[str, Sequence],
    key,
    *,
    replicas: int = 4,
    engine: Optional[str] = None,
    backend: Optional[str] = None,
    execution: Optional[Execution] = None,
    steps: Optional[int] = None,
) -> FleetGridResult:
    """Product sweep over fleet axes — ONE compile, named axes.

    ``over`` maps axis name → values for any of ``expiration_threshold``
    (scalar, broadcast to all functions, or a length-F sequence),
    ``n_cluster``, ``sim_time``, ``skip_time``.  The result grid gains a
    trailing named ``function`` axis (selectable by catalog name or
    positional index in :meth:`GridResult.sel`).
    """
    plan, _, bspec = _resolve_fleet(execution, engine, backend)
    _validate_axes(fleet, over)
    axis_vals, cells, outs = _fleet_cells(
        fleet, over, key, replicas, plan, bspec, steps
    )
    F = len(fleet.functions)
    names = list(axis_vals)
    dims = tuple(len(axis_vals[n]) for n in names)
    n_cells = len(outs)

    summaries = np.empty((n_cells, F), dtype=object)
    metric = lambda: np.zeros((n_cells, F))
    grids = {
        m: metric()
        for m in (
            "cold_start_prob",
            "rejection_prob",
            "avg_server_count",
            "avg_running_count",
            "avg_idle_count",
            "wasted_ratio",
            "avg_response_time",
            "developer_cost",
            "provider_cost",
            "goodput",
            "availability",
            "queue_wait_avg",
            "cluster_utilization",
            "peak_cluster",
        )
    }
    for c, out in enumerate(outs):
        fsum = _fleet_summary(fleet, cells, out, c)
        costs = _fleet_costs(fleet, fsum.summaries)
        qwa = fsum.queue_wait_avg
        for f, s in enumerate(fsum.summaries):
            summaries[c, f] = s
            grids["cold_start_prob"][c, f] = s.cold_start_prob
            grids["rejection_prob"][c, f] = s.rejection_prob
            grids["avg_server_count"][c, f] = s.avg_server_count
            grids["avg_running_count"][c, f] = s.avg_running_count
            grids["avg_idle_count"][c, f] = s.avg_idle_count
            grids["wasted_ratio"][c, f] = s.avg_wasted_ratio
            grids["avg_response_time"][c, f] = s.avg_response_time
            grids["developer_cost"][c, f] = costs[f].developer_total
            grids["provider_cost"][c, f] = costs[f].provider_infra_cost
            grids["goodput"][c, f] = s.goodput
            grids["availability"][c, f] = s.availability
            grids["queue_wait_avg"][c, f] = qwa[f]
            grids["cluster_utilization"][c, f] = fsum.cluster_utilization
            grids["peak_cluster"][c, f] = fsum.max_peak_cluster

    shape = dims + (F,)
    grids = {m: g.reshape(shape) for m, g in grids.items()}
    ok = np.ones(shape, bool)
    for g in grids.values():
        ok &= np.isfinite(g)

    return FleetGridResult(
        axes={**{n: tuple(axis_vals[n]) for n in names}, "function": fleet.names},
        replicas=replicas,
        backend=bspec.name,
        summaries=summaries.reshape(shape),
        cold_start_prob=grids["cold_start_prob"],
        rejection_prob=grids["rejection_prob"],
        avg_server_count=grids["avg_server_count"],
        avg_running_count=grids["avg_running_count"],
        avg_idle_count=grids["avg_idle_count"],
        wasted_ratio=grids["wasted_ratio"],
        avg_response_time=grids["avg_response_time"],
        developer_cost=grids["developer_cost"],
        provider_cost=grids["provider_cost"],
        goodput=grids["goodput"],
        availability=grids["availability"],
        ok=ok,
        execution=plan,
        queue_wait_avg=grids["queue_wait_avg"],
        cluster_utilization=grids["cluster_utilization"],
        peak_cluster=grids["peak_cluster"],
    )
