"""The unified Scenario/Experiment API (DESIGN.md §8).

The paper positions SimFaaS as the tool you reach for *instead of* a real
platform: "describe workload + platform, get metrics" should be one call.
This module is that front door:

* :class:`Scenario` — one declarative, frozen description of a simulation:
  the arrival process *or* a rate profile, the service/cold-start
  processes, platform limits, horizon/warm-up, metric windows and billing.
* :func:`run` — execute one scenario under an :class:`Execution` plan:
  any registered engine (``scan`` steady-state, ``temporal`` transient,
  ``par`` concurrency-value) × backend (``scan`` f64, ``pallas``/``ref``
  f32 block engine), returning a :class:`Result` bundling the summary and
  its cost estimate.  *How to execute* lives in
  :mod:`repro.core.execution` (DESIGN.md §9) — this module only consumes
  resolved plans; ``engine=``/``backend=`` kwargs are a thin layer that
  builds one.
* :func:`sweep` — an arbitrary product grid over scenario fields
  (``over={"expiration_threshold": [...], "arrival_rate": [...],
  "sim_time": [...], "profile": [...]}``) returning a :class:`GridResult`
  with named axes (``.sel(axis=value)`` selection, ``.to_dict()``
  export).  ``Execution(devices=..., shard="grid")`` splits the flattened
  grid axis across a 1-D device mesh — one compile, bitwise-equal per
  cell to the single-device sweep.

``sweep`` auto-partitions swept fields (see ``_STATIC_FIELDS`` /
``_DRAW_FIELDS`` / ``_PARAM_FIELDS``):

* **static** fields (``slots``, ``max_concurrency``, ``routing``, …)
  change the compiled program — each combination recompiles, looping in
  Python on the outermost grid axis;
* **draw** fields (``arrival_rate``, ``profile``, ``expiration_threshold``,
  the processes themselves) change the per-cell workload draws — one key
  split per cell, in the same chained order as the legacy per-cell loop,
  so grids are cell-by-cell reproducible against ``whatif.sweep_legacy``;
* **param** fields (``sim_time``, ``skip_time``) are pure traced values:
  cells along these axes *share* the draw-field cells' sample buffers
  (common random numbers across horizons) and only move
  :class:`WorkloadParams` columns.

Everything that is not static is flattened onto the single vmapped grid
axis of ``simulator._simulate_sweep`` — a (threshold × rate × horizon)
product grid is ONE compile and ONE device call, pinned by
``TRACE_COUNTS``.

The compile-time/run-time machinery lives here too: :class:`StaticConfig`
(hashable jit structure) and :class:`WorkloadParams` (traced pytree) are
the two halves every engine consumes.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import warnings
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import BillingModel, CostEstimate, estimate_cost
from repro.core.execution import Execution, plan_of, resolve_backend
from repro.core.faults import CapacityProfile, FaultModel
from repro.core.processes import (
    ArrivalTimeProcess,
    ExpSimProcess,
    NHPPArrivalProcess,
    RateProfile,
    SimProcess,
)
from repro.core.reliability import NO_TIMEOUT, Reliability

Array = jax.Array

# Python-side trace counters: incremented when a jitted entry point is
# (re-)traced, untouched on compile-cache hits.  Tests assert a whole
# what-if sweep costs exactly one trace.
TRACE_COUNTS: collections.Counter = collections.Counter()

# Host spans at the sweep's layer boundaries (``repro.sweep.stage`` /
# ``guard`` / ``launch`` / ``fetch`` / ``assemble``), written into the
# profiler's trace on the device trace's clock; with no trace running each
# is one TraceMe enter/exit.  None adds a sync or sits in a per-cell loop.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass(frozen=True)
class StaticConfig:
    """Compile-time structure of the simulation (hashable jit static arg).

    Everything here changes the *shape or code* of the compiled program.
    Workload parameters (rates, threshold, horizon) are deliberately NOT
    part of this class — they are traced values in ``WorkloadParams``.
    """

    slots: int
    max_concurrency: int
    routing: str
    scan_unroll: int
    track_histogram: bool
    hist_bins: int
    # prestamped: the scan consumes absolute arrival timestamps (f64) in
    # place of inter-arrival gaps — the non-stationary/trace-replay path.
    prestamped: bool = False
    # number of metric windows (0 = windowed metrics off); the window
    # *boundaries* are traced values in WorkloadParams.window_bounds.
    n_windows: int = 0
    # reliability layer (DESIGN.md §11): when True the step consumes a
    # per-event failure uniform and applies the traced timeout; the
    # *values* (t_timeout, p_fail, backoffs) stay in WorkloadParams.
    reliability: bool = False
    # retry budget — static because it sets the attempt-table width
    # (each base arrival expands to max_retries+1 pre-sorted events).
    max_retries: int = 0
    # platform-fault layer (DESIGN.md §15): when True the step carries a
    # per-slot crash time and consumes a per-event crash uniform; the
    # crash *rate* stays traced in WorkloadParams.
    crashes: bool = False
    # number of capacity-profile segments (0 = capacity churn off); the
    # edge times and ceilings themselves are traced values.
    cap_steps: int = 0


@dataclasses.dataclass(frozen=True)
class WorkloadParams:
    """Dynamic (traced) workload parameters — a jit-transparent pytree.

    Leaves are f64 scalars for a single run, or ``[C]`` vectors for a
    batched what-if sweep (one entry per grid row).  Changing these values
    never triggers recompilation.
    """

    expiration_threshold: Array
    sim_time: Array
    skip_time: Array
    # Metric-window boundaries: f64 ``[W+1]`` for a single run (shared by
    # replicas) or ``[C, W+1]`` for a sweep; ``[0]`` / ``[C, 0]`` when
    # windowed metrics are off (StaticConfig.n_windows == 0).
    window_bounds: Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((0,), dtype=jnp.float64)
    )
    # Reliability values (DESIGN.md §11).  All default to inert sentinels
    # so a scenario without a reliability policy carries them for free:
    # min(service, NO_TIMEOUT) is the bitwise identity and p_fail=0 never
    # fires.  The backoff triple is carried for introspection/sweep
    # bookkeeping — backoffs shape the pre-built attempt table (host-side,
    # per draw cell), not the in-step arithmetic.
    t_timeout: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(NO_TIMEOUT, dtype=jnp.float64)
    )
    p_fail: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0.0, dtype=jnp.float64)
    )
    backoff_base: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(1.0, dtype=jnp.float64)
    )
    backoff_mult: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(2.0, dtype=jnp.float64)
    )
    backoff_jitter: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0.0, dtype=jnp.float64)
    )
    # Platform-fault values (DESIGN.md §15): the crash hazard rate and the
    # capacity-profile step times/ceilings.  crash_rate=0 is inert; the
    # capacity arrays are [E]/[E+1] for a single run (shared by replicas),
    # [C, E]/[C, E+1] for a sweep, and empty when churn is off
    # (StaticConfig.cap_steps == 0).
    crash_rate: Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0.0, dtype=jnp.float64)
    )
    cap_edges: Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((0,), dtype=jnp.float64)
    )
    cap_values: Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((0,), dtype=jnp.float64)
    )

    @classmethod
    def of(
        cls,
        expiration_threshold,
        sim_time,
        skip_time,
        window_bounds=None,
        t_timeout=None,
        p_fail=None,
        backoff_base=None,
        backoff_mult=None,
        backoff_jitter=None,
        crash_rate=None,
        cap_edges=None,
        cap_values=None,
    ) -> "WorkloadParams":
        as64 = lambda x: jnp.asarray(x, dtype=jnp.float64)
        thr = as64(expiration_threshold)
        wb = (
            as64(window_bounds)
            if window_bounds is not None
            else jnp.zeros((0,), dtype=jnp.float64)
        )
        # Reliability defaults broadcast to the threshold's shape so every
        # leaf shares the sweep's leading [C] axis (vmap requirement).
        fill = lambda x, d: (
            jnp.full(thr.shape, d, jnp.float64) if x is None else as64(x)
        )
        empty = lambda x: (
            jnp.zeros((0,), dtype=jnp.float64) if x is None else as64(x)
        )
        return cls(
            thr,
            as64(sim_time),
            as64(skip_time),
            wb,
            fill(t_timeout, NO_TIMEOUT),
            fill(p_fail, 0.0),
            fill(backoff_base, 1.0),
            fill(backoff_mult, 2.0),
            fill(backoff_jitter, 0.0),
            fill(crash_rate, 0.0),
            empty(cap_edges),
            empty(cap_values),
        )


jax.tree_util.register_dataclass(
    WorkloadParams,
    data_fields=(
        "expiration_threshold",
        "sim_time",
        "skip_time",
        "window_bounds",
        "t_timeout",
        "p_fail",
        "backoff_base",
        "backoff_mult",
        "backoff_jitter",
        "crash_rate",
        "cap_edges",
        "cap_values",
    ),
    meta_fields=(),
)


def _rated(process: SimProcess, rate: float) -> SimProcess:
    """Re-rate an arrival process; fall back to exponential when the
    family has no rate handle (the legacy what-if behaviour)."""
    try:
        return process.with_rate(float(rate))
    except NotImplementedError:
        return ExpSimProcess(rate=float(rate))


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One declarative description of a serverless simulation experiment.

    Workload: either ``arrival_process`` (any :class:`SimProcess`,
    including timestamp processes such as NHPP/MMPP/trace replay) or
    ``rate_profile`` (a :class:`RateProfile`, lowered to
    ``NHPPArrivalProcess``); ``arrival_rate`` optionally re-rates a
    stationary arrival family (preserving its shape via ``with_rate``).

    Platform: ``expiration_threshold``, ``max_concurrency``, ``slots``,
    ``routing``, ``concurrency_value`` (requests per instance — the par
    engine's Knative-style knob).  Horizon: ``sim_time`` / ``skip_time``.
    Metrics: ``window_bounds``, ``track_histogram``.  Billing: a
    :class:`BillingModel` consumed by :func:`run`/:func:`sweep` cost
    grids.

    Not passed to jit directly: ``static_config()`` extracts the hashable
    compile-time structure and ``workload_params()`` the traced run-time
    values (module docstring).
    """

    arrival_process: Optional[SimProcess] = None
    warm_service_process: Optional[SimProcess] = None
    cold_service_process: Optional[SimProcess] = None
    expiration_threshold: float = 600.0
    max_concurrency: int = 1000
    sim_time: float = 1e5
    skip_time: float = 100.0  # warm-up transient excluded from metrics
    slots: int = 64  # instance-pool array size (>= peak live instances)
    # warm routing policy: "newest" (paper / McGrath & Brenner priority
    # scheduling) or "oldest" (LRU-like) — §Routing study
    routing: str = "newest"
    scan_unroll: int = 1  # lax.scan unroll factor (perf knob, semantics-free)
    track_histogram: bool = False
    hist_bins: int = 65  # instance-count histogram bins [0, hist_bins)
    # Windowed-metrics grid: W+1 ascending boundaries; per-window cold-start
    # probability / arrival counts / mean instance counts are reported in
    # SimulationSummary.windows.  None = off.
    window_bounds: Optional[tuple] = None
    # Declarative workload conveniences (resolved into arrival_process):
    rate_profile: Optional[RateProfile] = None
    arrival_rate: Optional[float] = None
    # Per-instance request concurrency (engine="par"); 1 = scale-per-request.
    concurrency_value: int = 1
    billing: BillingModel = BillingModel()
    # Failure/timeout/retry model (DESIGN.md §11); None = ideal platform.
    reliability: Optional[Reliability] = None
    # Platform fault injection (DESIGN.md §15); None = faultless platform.
    # FaultModel() (all defaults) is bitwise-identical to None.
    faults: Optional[FaultModel] = None

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if not self.sim_time > 0:
            raise ValueError(f"sim_time must be > 0, got {self.sim_time}")
        if self.skip_time < 0:
            raise ValueError(f"skip_time must be >= 0, got {self.skip_time}")
        if self.skip_time >= self.sim_time:
            raise ValueError("skip_time must be < sim_time")
        if not self.expiration_threshold > 0:
            raise ValueError(
                f"expiration_threshold must be > 0, got {self.expiration_threshold}"
            )
        if self.max_concurrency < 1:
            raise ValueError("max_concurrency must be >= 1")
        if self.hist_bins < 1:
            raise ValueError("hist_bins must be >= 1")
        if self.scan_unroll < 1:
            raise ValueError("scan_unroll must be >= 1")
        if self.arrival_rate is not None and not self.arrival_rate > 0:
            raise ValueError(
                f"arrival_rate must be > 0, got {self.arrival_rate}"
            )
        if self.reliability is not None and not isinstance(
            self.reliability, Reliability
        ):
            raise ValueError(
                "Scenario.reliability must be a Reliability (or None), got "
                f"{type(self.reliability).__name__}"
            )
        if self.concurrency_value < 1:
            raise ValueError("concurrency_value must be >= 1")
        if self.faults is not None:
            if not isinstance(self.faults, FaultModel):
                raise ValueError(
                    "Scenario.faults must be a FaultModel (or None), got "
                    f"{type(self.faults).__name__}"
                )
            if self.faults.enabled and self.window_bounds is not None:
                raise ValueError(
                    "platform faults do not serve windowed metrics yet; "
                    "drop window_bounds or the FaultModel"
                )
            if self.faults.enabled and self.track_histogram:
                raise ValueError(
                    "platform faults do not serve the instance-count "
                    "histogram; drop track_histogram or the FaultModel"
                )
        if self.window_bounds is not None:
            wb = np.asarray(self.window_bounds, dtype=np.float64)
            if wb.ndim != 1 or len(wb) < 2 or (np.diff(wb) <= 0).any():
                raise ValueError(
                    "window_bounds must be >= 2 strictly increasing values"
                )
            object.__setattr__(self, "window_bounds", tuple(float(b) for b in wb))
        if self.warm_service_process is None or self.cold_service_process is None:
            raise ValueError(
                "Scenario needs warm_service_process and cold_service_process"
            )
        ap = self.arrival_process
        if ap is None:
            if self.rate_profile is None:
                raise ValueError(
                    "Scenario needs an arrival_process or a rate_profile"
                )
            ap = NHPPArrivalProcess(profile=self.rate_profile)
        elif self.rate_profile is not None and not (
            isinstance(ap, NHPPArrivalProcess)
            and ap.profile == self.rate_profile
        ):
            # (an already-resolved profile round-trips through replace/of)
            raise ValueError(
                "give either arrival_process or rate_profile, not both"
            )
        if self.arrival_rate is not None:
            if isinstance(ap, ArrivalTimeProcess):
                # NHPP re-levels its profile shape-preservingly via
                # with_rate; MMPP/trace have no rate handle and must not
                # silently fall back to exponential (_rated would).
                try:
                    ap = ap.with_rate(float(self.arrival_rate))
                except NotImplementedError:
                    raise ValueError(
                        "arrival_rate cannot re-rate a timestamp process "
                        f"({type(ap).__name__}); sweep over rate profiles "
                        "instead"
                    ) from None
            else:
                ap = _rated(ap, self.arrival_rate)
            # Fold the rate into the process and clear the field: a stale
            # arrival_rate would silently re-rate any later
            # replace(arrival_process=...) override (e.g. a per-cell grid
            # re-rating) back to the old value.
            object.__setattr__(self, "arrival_rate", None)
        object.__setattr__(self, "arrival_process", ap)

    @classmethod
    def of(cls, config, **changes) -> "Scenario":
        """A plain Scenario copied from any Scenario-shaped config, with
        field overrides applied."""
        kw = {f.name: getattr(config, f.name) for f in dataclasses.fields(cls)}
        kw.update(changes)
        return Scenario(**kw)

    @property
    def prestamped(self) -> bool:
        """True when the arrival process yields absolute timestamps."""
        return isinstance(self.arrival_process, ArrivalTimeProcess)

    def steps_needed(self) -> int:
        """Upper bound on arrivals within ``sim_time`` (mean + 6 sigma)."""
        m = self.arrival_process.mean()
        n = self.sim_time / m
        return int(n + 6.0 * np.sqrt(max(n, 1.0)) + 16)

    def static_config(self) -> StaticConfig:
        """The compile-relevant slice of this config."""
        rel = self.reliability
        retries = int(rel.retry.max_retries) if rel is not None else 0
        flt = self.faults
        return StaticConfig(
            slots=self.slots,
            max_concurrency=self.max_concurrency,
            routing=self.routing,
            scan_unroll=self.scan_unroll,
            track_histogram=self.track_histogram,
            hist_bins=self.hist_bins,
            # a retry stream is a pre-sorted absolute-time attempt table
            prestamped=self.prestamped or retries > 0,
            n_windows=len(self.window_bounds) - 1 if self.window_bounds else 0,
            reliability=rel is not None,
            max_retries=retries,
            crashes=flt.crashes if flt is not None else False,
            cap_steps=flt.cap_steps if flt is not None else 0,
        )

    def workload_params(self) -> WorkloadParams:
        """The traced (run-time) slice of this config."""
        rel = self.reliability
        flt = self.faults
        cap = flt.capacity if flt is not None else None
        return WorkloadParams.of(
            self.expiration_threshold,
            self.sim_time,
            self.skip_time,
            self.window_bounds,
            t_timeout=rel.failure.timeout_or_inf if rel else None,
            p_fail=rel.failure.p_fail if rel else None,
            backoff_base=rel.retry.backoff_base if rel else None,
            backoff_mult=rel.retry.backoff_mult if rel else None,
            backoff_jitter=rel.retry.backoff_jitter if rel else None,
            crash_rate=flt.crash_rate if flt is not None else None,
            cap_edges=cap.edges if cap is not None else None,
            cap_values=cap.values if cap is not None else None,
        )


# ---------------------------------------------------------------------------
# run(): one scenario, one call, any engine × backend
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Result:
    """One scenario's outcome: the summary plus its cost estimate."""

    scenario: Scenario
    summary: Any  # SimulationSummary (or ParSimulationSummary)
    cost: CostEstimate
    temporal: Optional[Any] = None  # TemporalSummary when engine="temporal"

    # convenience passthroughs (the paper's headline metrics)
    @property
    def cold_start_prob(self) -> float:
        return self.summary.cold_start_prob

    @property
    def rejection_prob(self) -> float:
        return self.summary.rejection_prob

    @property
    def avg_server_count(self) -> float:
        return self.summary.avg_server_count

    @property
    def avg_running_count(self) -> float:
        return self.summary.avg_running_count

    @property
    def avg_response_time(self) -> float:
        return self.summary.avg_response_time

    @property
    def avg_wasted_ratio(self) -> float:
        return self.summary.avg_wasted_ratio

    @property
    def windows(self):
        return self.summary.windows

    def to_dict(self) -> dict:
        return {
            **self.summary.to_dict(),
            "developer_cost": self.cost.developer_total,
            "provider_cost": self.cost.provider_infra_cost,
        }


def run(
    scenario: Scenario,
    key,
    *,
    replicas: int = 8,
    engine: Optional[str] = None,
    backend: Optional[str] = None,
    execution: Optional[Execution] = None,
    steps: Optional[int] = None,
    grid=None,
    initial_instances: Sequence = (),
) -> Result:
    """Run one scenario under an :class:`Execution` plan.

    ``execution`` names the engine (simulation semantics), backend
    (execution substrate), precision and chunking; both are resolved
    through the registry in :mod:`repro.core.execution`, so unknown names
    raise with the registered list and invalid engine × backend pairs
    raise with the engine's declared capability.  The legacy ``engine=`` /
    ``backend=`` string kwargs build (or override) the plan:

    * ``engine="scan"`` — steady-state scale-per-request
      (:class:`ServerlessSimulator`); backends ``"scan"`` (f64 exact),
      ``"pallas"``/``"ref"`` (f32 block engine).
    * ``engine="temporal"`` — transient analysis with a custom initial
      pool (``initial_instances``) and point-in-time curves on ``grid``
      (default: 33 points over the horizon).  Declares scan-backend only.
    * ``engine="par"`` — concurrency-value platforms
      (``scenario.concurrency_value`` requests per instance).  Declares
      scan-backend only.
    """
    plan = plan_of(execution, engine, backend)
    espec, _ = plan.resolve()
    if plan.shard is not None:
        raise ValueError(
            "shard= applies to sweep() (there is no grid axis to split "
            "in a single run)"
        )
    scn = Scenario.of(scenario)
    if scn.faults is not None and scn.faults.enabled:
        if plan.backend not in espec.faults_backends:
            raise ValueError(
                f"engine {plan.engine!r} does not serve platform faults on "
                f"backend {plan.backend!r}; fault-capable backends "
                f"(EngineSpec.faults_backends): "
                f"{espec.faults_backends or '()'}"
            )
        if plan.resolved_draws == "fused":
            raise ValueError(
                "draws='fused' does not serve platform faults (the crash "
                "stream is host-staged); use draws='staged'"
            )
    summary, temporal = espec.run(
        scn,
        key,
        plan,
        replicas=replicas,
        steps=steps,
        grid=grid,
        initial_instances=initial_instances,
    )
    return Result(
        scenario=scn,
        summary=summary,
        cost=estimate_cost(summary, scn.billing),
        temporal=temporal,
    )


def _fused_stream_state(scn, key, replicas, n):
    """Lower a scenario to the block backends' fused-draw launch dict.

    The entire per-row sample state is three (four with a failure stream)
    uint32 key pairs plus the f32 distribution params — the O(C·K) staged
    buffers never exist (DESIGN.md §12).  Rejects arrival families the
    kernels cannot thin inline (NHPP needs ``profile.rate(t)`` at trace
    time — scan-engine only).
    """
    from repro.core import drawplan as dp

    fplan, pvals = dp.lower_scenario(scn)
    if fplan.arrival.kind == "nhpp":
        raise ValueError(
            "fused NHPP thinning is scan-backend only (the block kernels "
            "have no profile.rate(t) at trace time); use backend='scan' "
            "or draws='staged'"
        )
    krows = dp.stream_row_keys(key, replicas, fail=fplan.fail)
    tile = lambda v: np.tile(np.asarray(v, np.float32), (replicas, 1))
    return dict(
        dists=fplan.dists,
        keys=(krows["arrival"], krows["warm"], krows["cold"]),
        params=(tile(pvals["arrival"]), tile(pvals["warm"]), tile(pvals["cold"])),
        fail_keys=krows.get("fail"),
        n_steps=int(n),
    )


def _run_block_single(scn, key, replicas, steps, plan):
    """Single-scenario f32 block-engine run (C = replicas rows)."""
    from repro.core.simulator import (
        SimulationSummary,
        draw_reliability_stream,
        draw_workload_samples,
    )

    if scn.window_bounds:
        raise ValueError(
            "windowed single runs need backend='scan' (block windowed "
            "grids are available through sweep())"
        )
    if scn.track_histogram:
        raise ValueError("histograms need the f64 scan backend")
    n = steps or scn.steps_needed()
    rel = scn.reliability
    flt = scn.faults if scn.faults is not None and scn.faults.enabled else None
    rows = lambda v: np.full((replicas,), v)
    if plan.resolved_draws == "fused":
        fused = _fused_stream_state(scn, key, replicas, n)
        kw = dict(
            max_concurrency=scn.max_concurrency,
            prestamped=False,
            n_windows=0,
        )
        acc, t_last = _block_launch(
            scn,
            rows(scn.expiration_threshold),
            rows(scn.sim_time),
            rows(scn.skip_time),
            None,
            None,
            None,
            resolve_backend(plan.backend),
            kw,
            block_k=plan.resolved_block_k(n),
            t_to_rows=rows(rel.failure.timeout_or_inf) if rel else None,
            pf_rows=rows(rel.failure.p_fail) if rel else None,
            fused=fused,
        )
        if (t_last < scn.sim_time).any():
            raise RuntimeError(
                "fused arrival stream ended before sim_time "
                f"(min final t {t_last.min():.1f} < {scn.sim_time}); "
                "pass a larger `steps`"
            )
    else:
        extras = ()
        if rel is not None:
            (dts, warms, colds), extras = draw_reliability_stream(
                scn, key, replicas, n
            )
        else:
            dts, warms, colds = draw_workload_samples(scn, key, replicas, n)
        prestamped = scn.prestamped or (
            rel is not None and rel.retry.max_retries > 0
        )
        if not prestamped:
            covered = np.asarray(dts, np.float64).sum(axis=1)
            if (covered < scn.sim_time).any():
                raise RuntimeError(
                    "pre-drawn arrivals ended before sim_time "
                    f"(min final t {covered.min():.1f} < {scn.sim_time}); "
                    "pass a larger `steps`"
                )
        kw = dict(
            max_concurrency=scn.max_concurrency,
            prestamped=prestamped,
            n_windows=0,
        )
        fault_kw = {}
        if flt is not None:
            from repro.core.simulator import draw_crash_uniforms

            cap = flt.capacity
            fault_kw = dict(
                crash_rate_rows=rows(flt.crash_rate) if flt.crashes else None,
                crash_u=(
                    draw_crash_uniforms(key, replicas, dts.shape[1])
                    if flt.crashes
                    else None
                ),
                cap_edges=(
                    np.tile(np.asarray(cap.edges, np.float64), (replicas, 1))
                    if cap is not None
                    else None
                ),
                cap_values=(
                    np.tile(np.asarray(cap.values, np.float64), (replicas, 1))
                    if cap is not None
                    else None
                ),
            )
        acc = _block_launch(
            scn,
            rows(scn.expiration_threshold),
            rows(scn.sim_time),
            rows(scn.skip_time),
            dts,
            warms,
            colds,
            resolve_backend(plan.backend),
            kw,
            block_k=plan.resolved_block_k(dts.shape[1]),
            t_to_rows=rows(rel.failure.timeout_or_inf) if rel else None,
            pf_rows=rows(rel.failure.p_fail) if rel else None,
            extras=extras,
            **fault_kw,
        )
    zeros = np.zeros((replicas,))
    rely_kw = {}
    if rel is not None:
        from repro.kernels.faas_event_step import ACC_COLS

        rely_kw = dict(
            n_timeout=acc[:, ACC_COLS + 0],
            n_fail=acc[:, ACC_COLS + 1],
            n_retry=acc[:, ACC_COLS + 2],
            n_abandon=acc[:, ACC_COLS + 3],
        )
    if flt is not None:
        from repro.kernels.faas_event_step import ACC_COLS, RELY_COLS

        fb = ACC_COLS + (RELY_COLS if rel is not None else 0)
        rely_kw.update(
            n_crash=acc[:, fb + 0],
            n_evict=acc[:, fb + 1],
            n_interrupt=acc[:, fb + 2],
        )
    return SimulationSummary(
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
        **rely_kw,
    )


# ---------------------------------------------------------------------------
# sweep(): arbitrary product grids with static/draw/param partitioning
# ---------------------------------------------------------------------------

# Fields that change the compiled program: each combination is a separate
# compile (outermost Python loop).
_STATIC_FIELDS = (
    "slots",
    "max_concurrency",
    "routing",
    "scan_unroll",
    "track_histogram",
    "hist_bins",
    "window_bounds",
)
# Fields that change the per-cell sample draws (one chained key split per
# cell, legacy-loop order).  expiration_threshold does not change draw
# *values* but stays in the chain for cell-by-cell reproducibility against
# the legacy per-cell loop.
_DRAW_FIELDS = (
    "expiration_threshold",
    "arrival_rate",
    "profile",
    "arrival_process",
    "warm_service_process",
    "cold_service_process",
)
_DRAW_FIELDS = _DRAW_FIELDS + (
    # Backoff parameters shape the pre-built attempt table, so each value
    # is its own draw cell (stream rebuild); the traced copies still ride
    # in WorkloadParams.  max_retries is static *and* changes the table
    # width, so it is not sweepable — split the sweep instead.
    "backoff_base",
    "backoff_mult",
    "backoff_jitter",
)
# Pure traced values: cells along these axes share the draw cells' sample
# buffers (common random numbers across horizons/warm-ups).  t_timeout and
# p_fail are pure per-row comparisons against pre-drawn uniforms, so a
# (t_timeout × threshold) reliability grid shares one set of draws and ONE
# compile.  crash_rate scales the shared crash uniforms into lifetimes
# per row, and capacity moves the traced profile edges/ceilings — so a
# (crash_rate × threshold) fault grid is likewise one trace (DESIGN.md
# §15); capacity values are CapacityProfile objects sharing a step count.
_PARAM_FIELDS = (
    "sim_time",
    "skip_time",
    "t_timeout",
    "p_fail",
    "crash_rate",
    "capacity",
)

# Axes that require Scenario.reliability to be set (the static flag and
# the failure uniforms come from it).
_RELY_AXES = (
    "t_timeout",
    "p_fail",
    "backoff_base",
    "backoff_mult",
    "backoff_jitter",
)

# Axes that require Scenario.faults to be set (the static fault structure
# and the crash-uniform stream come from it).
_FAULT_AXES = ("crash_rate", "capacity")


@dataclasses.dataclass
class GridResult:
    """Named-axis product-grid results (one entry per ``over`` axis).

    Every metric array has shape ``dims = tuple(len(v) for v in
    axes.values())`` in the ``over`` insertion order; ``summaries`` is an
    object array of per-cell :class:`SimulationSummary` (replica axes
    pooled inside each cell).  Windowed arrays carry a trailing ``W`` axis
    and are ``None`` when the scenario has no ``window_bounds`` (or when
    ``window_bounds`` itself is swept).
    """

    axes: dict  # name -> tuple of swept values, insertion order = dims
    replicas: int
    backend: str
    summaries: np.ndarray  # object[*dims]
    cold_start_prob: np.ndarray  # [*dims]
    rejection_prob: np.ndarray
    avg_server_count: np.ndarray
    avg_running_count: np.ndarray
    avg_idle_count: np.ndarray
    wasted_ratio: np.ndarray
    avg_response_time: np.ndarray
    developer_cost: np.ndarray
    provider_cost: np.ndarray
    goodput: Optional[np.ndarray] = None  # [*dims] completions/s
    availability: Optional[np.ndarray] = None  # [*dims] 1 - crash-interrupt share
    ok: Optional[np.ndarray] = None  # [*dims] all-finite-metrics mask
    window_bounds: Optional[np.ndarray] = None  # [W+1]
    windowed_cold_prob: Optional[np.ndarray] = None  # [*dims, W]
    windowed_arrivals: Optional[np.ndarray] = None  # [*dims, W] replica-mean
    windowed_instance_count: Optional[np.ndarray] = None  # [*dims, W]
    execution: Optional[Execution] = None  # the resolved plan (block_k filled)

    # grid fields indexed by the named axes (in order); windowed ones carry
    # a trailing [W] axis that selection leaves untouched
    _METRIC_FIELDS = (
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
        "ok",
    )
    _WINDOWED_FIELDS = (
        "windowed_cold_prob",
        "windowed_arrivals",
        "windowed_instance_count",
    )

    @property
    def shape(self) -> tuple:
        return tuple(len(v) for v in self.axes.values())

    def axis(self, name: str) -> tuple:
        if name not in self.axes:
            raise KeyError(
                f"unknown axis {name!r}; axes: {list(self.axes)}"
            )
        return self.axes[name]

    def _index_of(self, name: str, value) -> int:
        if name not in self.axes:
            raise KeyError(
                f"unknown axis {name!r}; axes: {list(self.axes)}"
            )
        vals = list(self.axes[name])
        try:
            return vals.index(value)
        except ValueError:
            # Positional fallback: fleet grids carry a categorical
            # ``function`` axis whose values are names — an int that is
            # not itself an axis value selects by position.
            if (
                isinstance(value, int)
                and not isinstance(value, bool)
                and 0 <= value < len(vals)
            ):
                return value
            raise KeyError(
                f"{value!r} is not on axis {name!r}; values: {vals}"
            ) from None

    def cell(self, **coords):
        """The per-cell summary at axis *values* (e.g. ``sim_time=500.0``)."""
        for name in self.axes:
            if name not in coords:
                raise KeyError(f"missing coordinate {name!r}")
        idx = tuple(self._index_of(n, coords[n]) for n in self.axes)
        return self.summaries[idx]

    def sel(self, **coords) -> "GridResult":
        """Named-axis selection by *value*: ``grid.sel(arrival_rate=1.0)``
        pins that axis and drops it from the result, so plots and reports
        never do raw index math.  Selecting every axis leaves scalar
        metric arrays (and the bare per-cell summary in ``summaries``)."""
        picked = {n: self._index_of(n, v) for n, v in coords.items()}
        indexer = tuple(
            picked.get(n, slice(None)) for n in self.axes
        )

        def take(a):
            return None if a is None else np.asarray(a)[indexer]

        return dataclasses.replace(
            self,
            axes={n: v for n, v in self.axes.items() if n not in picked},
            summaries=self.summaries[indexer],
            **{f: take(getattr(self, f)) for f in self._METRIC_FIELDS},
            **{f: take(getattr(self, f)) for f in self._WINDOWED_FIELDS},
        )

    def to_dict(self) -> dict:
        """JSON-able export: axes (non-scalar values stringified), every
        scalar metric grid (including the ``ok`` non-finite mask), the
        resolved execution plan's ``block_k``/``draws``, and the windowed
        grids when present."""
        jsonable = lambda x: (
            x if isinstance(x, (int, float, str, bool)) else repr(x)
        )
        out = {
            "axes": {n: [jsonable(x) for x in v] for n, v in self.axes.items()},
            "replicas": self.replicas,
            "backend": self.backend,
        }
        if self.execution is not None:
            out["block_k"] = self.execution.block_k
            out["draws"] = self.execution.resolved_draws
        for f in self._METRIC_FIELDS + self._WINDOWED_FIELDS:
            a = getattr(self, f)
            if a is not None:
                out[f] = np.asarray(a).tolist()
        if self.window_bounds is not None:
            out["window_bounds"] = np.asarray(self.window_bounds).tolist()
        return out


class PendingSweep:
    """A dispatched-but-not-yet-drained :func:`sweep`.

    ``sweep(..., deferred=True)`` returns one of these immediately after
    the jitted device call(s) are enqueued (JAX async dispatch);
    :meth:`result` blocks on the device→host transfer and assembles the
    :class:`GridResult`.  Because the deferred path dispatches the exact
    same executable on the exact same operands, ``result()`` is
    bitwise-equal to the synchronous sweep.  ``result()`` memoizes, so
    draining twice is free.
    """

    def __init__(self, finish):
        self._finish = finish
        self._result: Optional[GridResult] = None

    def result(self) -> GridResult:
        if self._result is None:
            self._result = self._finish()
            self._finish = None  # drop the captured device buffers
        return self._result


def _apply_axis(scn: Scenario, name: str, value) -> Scenario:
    """One scenario-field override, with the workload conveniences."""
    if name == "profile":
        if not isinstance(value, RateProfile):
            raise TypeError(f"expected RateProfile, got {type(value).__name__}")
        return Scenario.of(
            scn,
            arrival_process=NHPPArrivalProcess(profile=value),
            rate_profile=None,
            arrival_rate=None,
        )
    if name == "arrival_process":
        if not isinstance(value, SimProcess):
            raise TypeError(f"expected SimProcess, got {type(value).__name__}")
        return Scenario.of(
            scn, arrival_process=value, rate_profile=None, arrival_rate=None
        )
    if name == "arrival_rate":
        return Scenario.of(scn, arrival_rate=float(value))
    if name in ("backoff_base", "backoff_mult", "backoff_jitter"):
        retry = dataclasses.replace(
            scn.reliability.retry, **{name: float(value)}
        )
        return Scenario.of(
            scn,
            reliability=dataclasses.replace(scn.reliability, retry=retry),
        )
    return Scenario.of(scn, **{name: value})


def sweep(
    scenario: Scenario,
    over: Mapping[str, Sequence],
    key,
    *,
    replicas: int = 4,
    backend: Optional[str] = None,
    execution: Optional[Execution] = None,
    steps: Optional[int] = None,
    deferred: bool = False,
):
    """Product-grid what-if sweep over arbitrary scenario fields.

    ``over`` maps field names to value lists; the result grid has one
    named axis per entry, in insertion order.  All non-static axes are
    flattened onto the single vmapped grid axis and executed as ONE
    compiled device call per static-field combination (module docstring
    has the partitioning rules).

    ``execution`` picks the substrate (backends as in :func:`run`; the
    legacy ``backend=`` kwarg overrides the plan's backend).  With
    ``Execution(devices=..., shard="grid")`` the flattened grid axis is
    split across a 1-D device mesh via ``shard_map`` — padded to a
    multiple of the device count, still one compile, and bitwise-equal
    per cell to the single-device sweep.

    ``deferred=True`` returns a :class:`PendingSweep` as soon as the
    device launch(es) are *enqueued* (JAX async dispatch) instead of a
    finished :class:`GridResult`; call ``.result()`` to drain.  Native
    scan backend only — the ops and executable are the synchronous
    path's, so the drained grid is bitwise-equal to ``deferred=False``.
    The online what-if service uses this to overlap a tick's simulation
    with arrival ingestion.
    """
    plan = plan_of(execution, None, backend)
    espec, bspec = plan.resolve()
    if not espec.sweepable:
        raise ValueError(
            f"engine {plan.engine!r} does not support sweep(); it runs "
            "single scenarios only (use run(), or engine='scan' for grids)"
        )
    if espec.name != "scan":
        # the flattened-grid machinery below IS the scan engine's; a
        # third-party engine declaring sweepable would otherwise silently
        # get scan semantics instead of its own
        raise ValueError(
            f"engine {plan.engine!r} declares sweepable but sweep() "
            "batching is implemented by the built-in 'scan' grid engine "
            "only; run() the engine per cell instead"
        )
    names = list(over.keys())
    if not names:
        raise ValueError("over must name at least one axis to sweep")
    vals = {}
    for n in names:
        if n not in _STATIC_FIELDS + _DRAW_FIELDS + _PARAM_FIELDS:
            raise ValueError(
                f"unknown sweep axis {n!r}; sweepable fields: "
                f"{_STATIC_FIELDS + _DRAW_FIELDS + _PARAM_FIELDS}"
            )
        vals[n] = tuple(over[n])
        if not vals[n]:
            raise ValueError(f"sweep axis {n!r} is empty")
    static_names = [n for n in names if n in _STATIC_FIELDS]
    draw_names = [n for n in names if n in _DRAW_FIELDS]
    param_names = [n for n in names if n in _PARAM_FIELDS]
    dims = {n: len(vals[n]) for n in names}
    base = Scenario.of(scenario)
    rely_axes = [n for n in names if n in _RELY_AXES]
    if rely_axes and base.reliability is None:
        raise ValueError(
            f"sweeping {rely_axes} needs Scenario.reliability= to be set "
            "on the base scenario (it provides the static reliability "
            "structure and the failure uniforms)"
        )
    for v in vals.get("t_timeout", ()):
        if not float(v) > 0:
            raise ValueError(f"t_timeout values must be > 0, got {v}")
    for v in vals.get("p_fail", ()):
        if not 0.0 <= float(v) < 1.0:
            raise ValueError(f"p_fail values must be in [0, 1), got {v}")

    # ---- platform-fault axes (DESIGN.md §15)
    fault_axes = [n for n in names if n in _FAULT_AXES]
    flt = base.faults
    if fault_axes and flt is None:
        raise ValueError(
            f"sweeping {fault_axes} needs Scenario.faults= to be set on "
            "the base scenario (it provides the static fault structure "
            "and the crash stream)"
        )
    for v in vals.get("crash_rate", ()):
        if not np.isfinite(float(v)) or float(v) < 0:
            raise ValueError(
                f"crash_rate values must be finite and >= 0, got {v}"
            )
    caps = tuple(vals.get("capacity", ()))
    for v in caps:
        if not isinstance(v, CapacityProfile):
            raise TypeError(
                "capacity axis values must be CapacityProfile, got "
                f"{type(v).__name__}"
            )
    if caps and len({len(v.values) for v in caps}) > 1:
        raise ValueError(
            "capacity profiles on one sweep axis must share a step count "
            "(len(values) is compile-time static); split the sweep"
        )
    crashes_on = flt is not None and (flt.crashes or "crash_rate" in names)
    cap_n = (
        len(caps[0].values)
        if caps
        else (flt.cap_steps if flt is not None else 0)
    )
    faults_on = crashes_on or cap_n > 0
    if faults_on:
        if plan.backend not in espec.faults_backends:
            raise ValueError(
                "platform faults are not served by engine "
                f"{plan.engine!r} on backend {plan.backend!r}; "
                "fault-capable backends (EngineSpec.faults_backends): "
                f"{espec.faults_backends or '()'}"
            )
        if plan.resolved_draws == "fused":
            raise ValueError(
                "draws='fused' does not serve platform faults (the crash "
                "stream is host-staged); use draws='staged'"
            )
        if base.window_bounds or "window_bounds" in names:
            raise ValueError(
                "platform faults do not serve windowed metrics yet; drop "
                "window_bounds or the fault axes"
            )
        if base.track_histogram or "track_histogram" in names:
            raise ValueError(
                "platform faults do not serve the instance-count "
                "histogram; drop track_histogram or the fault axes"
            )

    # ---- draw cells: product over draw axes, one chained key split each
    draw_combos = list(
        itertools.product(*[vals[n] for n in draw_names])
    ) or [()]
    draw_cfgs = []
    for combo in draw_combos:
        c = base
        for n, v in zip(draw_names, combo):
            c = _apply_axis(c, n, v)
        draw_cfgs.append(c)
    stamped = {c.prestamped for c in draw_cfgs}
    if len(stamped) > 1:
        raise ValueError(
            "cannot mix stationary and timestamp arrival processes in one "
            "grid; split the sweep"
        )
    prestamped = stamped.pop()

    sim_vals = vals.get("sim_time", (base.sim_time,))
    skip_vals = vals.get("skip_time", (base.skip_time,))
    if max(skip_vals) >= min(sim_vals):
        raise ValueError("every skip_time must be < every sim_time on the grid")
    max_sim = float(max(sim_vals))

    from repro.core.simulator import (
        check_coverage,
        draw_crash_uniforms,
        draw_reliability_stream,
        draw_workload_samples,
        stage_draw_cells,
    )
    from repro.core.drawplan import lower_staged

    n_steps = (
        int(steps)
        if steps is not None
        else max(
            Scenario.of(c, sim_time=max_sim).steps_needed() for c in draw_cfgs
        )
    )
    if bspec.kind == "block":
        # pin the concrete (possibly auto-selected) chunk size on the plan
        # so GridResult.execution reports what actually ran
        plan = dataclasses.replace(
            plan, block_k=plan.resolved_block_k(n_steps)
        )
    # pin the resolved draw mode too (None -> "staged")
    plan = dataclasses.replace(plan, draws=plan.resolved_draws)
    fused_mode = plan.draws == "fused"
    R = int(replicas)
    D = len(draw_cfgs)
    rel = base.reliability
    retries = int(rel.retry.max_retries) if rel is not None else 0
    if retries > 0:
        # the attempt table is absolute f64 times — the whole grid runs
        # prestamped regardless of the base arrival process
        prestamped = True
    # ---- host staging: the draw stacks (or fused key rows) and the
    # per-row columns; enqueued device programs ride inside the span.
    # Without a retry table or a crash stream, the draw cells are staged
    # by one device program when their processes lower to it (see
    # simulator.stage_draw_cells); the span's ``batched`` says which.
    # ``coverage`` then checks each draw row's reach, reduced on the
    # device, once the caller has the vector.
    with _span("repro.sweep.stage", cells=D) as stage_span:
        lowered = coverage = None
        if not fused_mode and retries == 0 and not crashes_on:
            lowered = lower_staged(draw_cfgs)
        stage_span.set_metadata(batched=int(lowered is not None))
        bufs = ()
        fplan = krows = pvals_list = None
        if lowered is not None:
            kinds, params = lowered
            bufs, cover = stage_draw_cells(
                key, params, kinds=kinds, replicas=R, n=n_steps,
                fail=rel is not None,
            )
            coverage = functools.partial(
                check_coverage, cover, max_sim, stamped=prestamped
            )
        elif fused_mode:
            from repro.core import drawplan as dpmod

            plans, pvals_list = [], []
            for c in draw_cfgs:
                fp, pv = dpmod.lower_scenario(c)  # rejects retries/unlowerable
                plans.append(fp)
                pvals_list.append(pv)
            if len(set(plans)) > 1:
                raise ValueError(
                    "fused draws compile one DrawPlan for the whole grid; "
                    "sweeping distribution families or rate profiles across "
                    "draw cells needs draws='staged'"
                )
            fplan = plans[0]
            if fplan.arrival.kind == "nhpp" and bspec.kind == "block":
                raise ValueError(
                    "fused NHPP thinning is scan-backend only (the block "
                    "kernels have no profile.rate(t) at trace time); use "
                    "backend='scan' or draws='staged'"
                )
            # fused streams are gap-based (NHPP thinning happens inline), so
            # the prestamped flag the staged NHPP path would set stays off
            prestamped = False
            kparts = []
            for c in draw_cfgs:
                key, sub = jax.random.split(key)  # same chained walk as staged
                kparts.append(dpmod.stream_row_keys(sub, R, fail=rel is not None))
            streams = ("arrival", "warm", "cold") + (
                ("fail",) if rel is not None else ()
            )
            # [D*R, 2] per stream — the whole grid's sample state
            krows = {
                s: jnp.concatenate([kp[s] for kp in kparts]) for s in streams
            }
        else:
            parts = []
            for c in draw_cfgs:
                key, sub = jax.random.split(key)
                c_sim = Scenario.of(c, sim_time=max_sim)
                if rel is not None:
                    smp_c, ext_c = draw_reliability_stream(c_sim, sub, R, n_steps)
                    part = tuple(smp_c) + tuple(ext_c)
                else:
                    part = tuple(draw_workload_samples(c_sim, sub, R, n_steps))
                if crashes_on:
                    # fold_in-salted off the cell key, so the base streams are
                    # bitwise-unchanged by the fault layer; positional per
                    # event (i.i.d.), so it need not ride the attempt-table
                    # sort — a cold start at event k consumes crash_u[k].
                    part = part + (
                        draw_crash_uniforms(sub, R, part[0].shape[1]),
                    )
                parts.append(part)
            # [D*R, K] per buffer; with retries K = n_steps * (max_retries + 1)
            bufs = tuple(
                jnp.concatenate([p[j] for p in parts]) for j in range(len(parts[0]))
            )

        # ---- param cells share draws: tile rows to C = D*Wn*R
        param_combos = list(
            itertools.product(*[vals[n] for n in param_names])
        ) or [()]
        Wn = len(param_combos)
        C = D * Wn * R

        def _param_col(name, default):
            if name in param_names:
                i = param_names.index(name)
                col = np.asarray([pc[i] for pc in param_combos], np.float64)
            else:
                col = np.full((Wn,), default, np.float64)
            return np.tile(np.repeat(col, R), D)  # [C]

        def _draw_col(values):
            return np.repeat(np.asarray(values, np.float64), Wn * R)  # [C]

        thr_rows = _draw_col([c.expiration_threshold for c in draw_cfgs])
        sim_rows = _param_col("sim_time", base.sim_time)
        skip_rows = _param_col("skip_time", base.skip_time)
        rely_rows = None
        if rel is not None:
            rely_rows = dict(
                t_timeout=_param_col("t_timeout", rel.failure.timeout_or_inf),
                p_fail=_param_col("p_fail", rel.failure.p_fail),
                backoff_base=_draw_col(
                    [c.reliability.retry.backoff_base for c in draw_cfgs]
                ),
                backoff_mult=_draw_col(
                    [c.reliability.retry.backoff_mult for c in draw_cfgs]
                ),
                backoff_jitter=_draw_col(
                    [c.reliability.retry.backoff_jitter for c in draw_cfgs]
                ),
            )
        fault_rows = None
        if faults_on:
            fault_rows = dict(crashes=crashes_on, cap_steps=cap_n)
            if crashes_on:
                fault_rows["crash_rate"] = _param_col(
                    "crash_rate", flt.crash_rate
                )
            if cap_n:
                if "capacity" in param_names:
                    i = param_names.index("capacity")
                    profs = [pc[i] for pc in param_combos]
                else:
                    profs = [flt.capacity] * Wn
                # [Wn, E] -> [C, E] in the (draw, param, replica) row order
                mat = lambda a: np.tile(
                    np.repeat(np.asarray(a, np.float64), R, axis=0), (D, 1)
                )
                fault_rows["cap_edges"] = mat([p.edges for p in profs])
                fault_rows["cap_values"] = mat([p.values for p in profs])

        def _expand(x):
            if Wn == 1:
                return x
            k = x.shape[1]  # per-buffer width: retries widen K past n_steps
            return jnp.repeat(x.reshape(D, 1, R, k), Wn, axis=1).reshape(C, k)

        samples = tuple(_expand(x) for x in bufs)

        fused_scan = fused_block = None
        if fused_mode:
            # [C, 2] per-stream key pairs / param pairs — the grid's whole
            # sample state; the O(C·K) buffers never exist
            krows_exp = {s: _expand(v) for s, v in krows.items()}
            pvals = {
                s: np.asarray([pv[s] for pv in pvals_list], np.float64)
                for s in ("arrival", "warm", "cold")
            }
            if bspec.kind == "native":
                prows_exp = {
                    s: jnp.asarray(np.repeat(v, Wn * R, axis=0))
                    for s, v in pvals.items()
                }
                fused_scan = (fplan, int(n_steps), krows_exp, prows_exp)
            else:
                fused_block = dict(
                    dists=fplan.dists,
                    keys=tuple(krows_exp[s] for s in ("arrival", "warm", "cold")),
                    params=tuple(
                        np.repeat(np.asarray(pvals[s], np.float32), Wn * R, axis=0)
                        for s in ("arrival", "warm", "cold")
                    ),
                    fail_keys=krows_exp.get("fail"),
                    n_steps=int(n_steps),
                )

    # ---- static combos: one compile each (outermost Python loop).
    # Native (scan) launches are *dispatched* here and drained in
    # _finish(); block launchers convert to numpy internally, so their
    # collector is the already-materialized result.
    static_combos = list(
        itertools.product(*[vals[n] for n in static_names])
    ) or [()]
    S = len(static_combos)
    if deferred and bspec.kind != "native":
        raise ValueError(
            "deferred=True needs the native scan backend (block backends "
            f"drain device results inside their launcher); got backend="
            f"{plan.backend!r}"
        )
    collectors: list = []
    shared_bounds: Optional[np.ndarray] = None
    for combo in static_combos:
        scn_s = base
        for n, v in zip(static_names, combo):
            scn_s = _apply_axis(scn_s, n, v)
        scfg = dataclasses.replace(
            scn_s.static_config(),
            prestamped=prestamped,
            # fault axes widen the static structure past the base model
            # (e.g. a crash_rate axis over a crash_rate=0 base)
            crashes=crashes_on,
            cap_steps=cap_n,
        )
        smp = (
            tuple(jnp.array(x, copy=True) for x in samples)
            if S > 1
            else samples
        )
        if bspec.kind == "native":
            collectors.append(
                _scan_dispatch(
                    scfg, scn_s, thr_rows, sim_rows, skip_rows, smp, R,
                    prestamped, plan, rely_rows=rely_rows, fused=fused_scan,
                    fault_rows=fault_rows, coverage=coverage,
                )
            )
        else:
            res = _block_cells(
                scn_s, thr_rows, sim_rows, skip_rows, smp, R, prestamped,
                bspec, plan, rely_rows=rely_rows, fused=fused_block,
                fault_rows=fault_rows, coverage=coverage,
            )
            collectors.append(lambda res=res: res)
        if "window_bounds" not in static_names and scn_s.window_bounds:
            shared_bounds = np.asarray(scn_s.window_bounds)

    def _finish() -> GridResult:
        all_summaries: list = []
        windowed: list = []
        for col in collectors:
            cells, win = col()
            all_summaries.extend(cells)
            windowed.append(win)
        with _span("repro.sweep.assemble"):
            return _assemble(all_summaries, windowed)

    def _assemble(all_summaries, windowed) -> GridResult:
        # ---- assemble the named-axis grid (internal order: static,
        # draw, param)
        internal_names = static_names + draw_names + param_names
        internal_dims = tuple(dims[n] for n in internal_names) or (1,)
        perm = [internal_names.index(n) for n in names]

        def _grid(values, trailing=0):
            arr = np.asarray(values).reshape(
                internal_dims + ((values.shape[-1],) if trailing else ())
            )
            return np.transpose(
                arr, perm + ([len(internal_dims)] if trailing else [])
            )

        billing = base.billing
        costs = [estimate_cost(s, billing) for s in all_summaries]
        metric = lambda f: _grid(
            np.asarray([f(s) for s in all_summaries], np.float64)
        )
        summaries_grid = np.empty((len(all_summaries),), dtype=object)
        summaries_grid[:] = all_summaries
        summaries_grid = _grid(summaries_grid)

        w_cold = w_arr = w_inst = None
        # Windowed grids need one shared window grid: a swept window_bounds
        # axis yields per-combo W's that cannot stack (summaries keep the
        # per-cell windows either way).
        if (
            "window_bounds" not in static_names
            and windowed
            and all(w is not None for w in windowed)
        ):
            stack = {
                k: np.concatenate([w[k] for w in windowed])
                for k in ("cold", "arrivals")
            }
            w_cold = _grid(stack["cold"], trailing=1)
            w_arr = _grid(stack["arrivals"], trailing=1)
            if all(w.get("instances") is not None for w in windowed):
                w_inst = _grid(
                    np.concatenate([w["instances"] for w in windowed]),
                    trailing=1,
                )

        metrics = dict(
            cold_start_prob=metric(lambda s: s.cold_start_prob),
            rejection_prob=metric(lambda s: s.rejection_prob),
            avg_server_count=metric(lambda s: s.avg_server_count),
            avg_running_count=metric(lambda s: s.avg_running_count),
            avg_idle_count=metric(lambda s: s.avg_idle_count),
            wasted_ratio=metric(lambda s: s.avg_wasted_ratio),
            avg_response_time=metric(lambda s: s.avg_response_time),
            developer_cost=_grid(
                np.asarray([c.developer_total for c in costs])
            ),
            provider_cost=_grid(
                np.asarray([c.provider_infra_cost for c in costs])
            ),
            goodput=metric(lambda s: s.goodput),
            availability=metric(lambda s: s.availability),
        )
        ok = np.ones(metrics["cold_start_prob"].shape, bool)
        for m in metrics.values():
            ok &= np.isfinite(m)
        if not ok.all():
            _warn_nonfinite({n: vals[n] for n in names}, ok)

        return GridResult(
            axes={n: vals[n] for n in names},
            replicas=R,
            backend=plan.backend,
            execution=plan,
            summaries=summaries_grid,
            **metrics,
            ok=ok,
            window_bounds=shared_bounds,
            windowed_cold_prob=w_cold,
            windowed_arrivals=w_arr,
            windowed_instance_count=w_inst,
        )

    if deferred:
        return PendingSweep(_finish)
    return _finish()


def _warn_nonfinite(axes: dict, ok: np.ndarray) -> None:
    """Name the grid cells whose metrics came back non-finite."""
    bad = np.argwhere(~ok)
    names = list(axes)
    cells = [
        "("
        + ", ".join(f"{n}={axes[n][i]!r}" for n, i in zip(names, idx))
        + ")"
        for idx in bad[:8]
    ]
    more = "" if len(bad) <= 8 else f" (+{len(bad) - 8} more)"
    warnings.warn(
        f"sweep produced non-finite metrics in {len(bad)} cell(s): "
        + ", ".join(cells) + more + "; see GridResult.ok",
        RuntimeWarning,
        stacklevel=3,
    )


def _scan_dispatch(
    scfg, scn_s, thr_rows, sim_rows, skip_rows, samples, R, prestamped, plan,
    rely_rows=None, fused=None, fault_rows=None, coverage=None,
):
    """Enqueue one f64 sweep launch; return a zero-arg collector.

    The jitted device call is *dispatched* (JAX async dispatch) before
    this returns — the collector then blocks on the device→host transfer
    (``np.asarray``) and builds the per-cell summaries.  Splitting the
    two lets ``sweep(deferred=True)`` overlap the simulation with host
    work (the online service ingests arrivals while the sweep runs);
    the ops and executable are identical either way, so a deferred
    sweep's results are bitwise-equal to the synchronous path's.

    ``plan.shard == "grid"`` runs the same vmapped scan under a
    ``shard_map`` over the plan's 1-D device mesh: the flattened row axis
    is padded (with copies of row 0, sliced off afterwards) to a multiple
    of the device count.  Rows are independent, so every real cell is
    bitwise-identical to the single-device launch.

    ``coverage`` (batched staging's check of its prestamped rows) runs in
    the collector after the fetch, so a deferred sweep raises from
    ``result()``; gap rows are checked on the final clock there.
    """
    from repro.core.simulator import (
        SimulationSummary,
        WindowedMetrics,
        check_coverage,
        sweep_executable,
    )

    C = len(thr_rows)
    wb = scn_s.window_bounds
    W = len(wb) - 1 if wb else 0
    wb_rows = (
        np.tile(np.asarray(wb, np.float64), (C, 1))
        if wb
        else np.zeros((C, 0))
    )
    rr = rely_rows or {}
    fr = fault_rows or {}
    # every leaf needs the sweep's leading [C] axis (vmap requirement),
    # so the capacity matrices default to [C, 0] like window_bounds
    ce = fr.get("cap_edges")
    cv = fr.get("cap_values")
    if ce is None:
        ce, cv = np.zeros((C, 0)), np.zeros((C, 0))
    params = WorkloadParams.of(
        thr_rows, sim_rows, skip_rows, wb_rows,
        t_timeout=rr.get("t_timeout"),
        p_fail=rr.get("p_fail"),
        backoff_base=rr.get("backoff_base"),
        backoff_mult=rr.get("backoff_mult"),
        backoff_jitter=rr.get("backoff_jitter"),
        crash_rate=fr.get("crash_rate"),
        cap_edges=ce,
        cap_values=cv,
    )
    if fused is not None:
        # one device execution over [C, 2] key/param rows; the counter
        # scan generates every draw inline (Execution.resolve() already
        # rejected fused × shard='grid')
        from repro.core.simulator import _simulate_sweep_fused

        fplan, n_f, krows, prows = fused
        with _span("repro.sweep.launch"):
            acc, t_last = _simulate_sweep_fused(
                scfg, fplan, n_f, params, krows, prows
            )
    else:
        mesh = None
        if plan.shard == "grid":
            mesh = plan.mesh()
            pad = (-C) % int(mesh.devices.size)
            if pad:
                pad_rows = lambda x: jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]
                )
                params = jax.tree.map(pad_rows, params)
                samples = tuple(pad_rows(x) for x in samples)
        fn = sweep_executable(mesh=mesh, donate=plan.donate)
        with warnings.catch_warnings(), _span("repro.sweep.launch"):
            # buffer donation is a no-op on CPU; the warning is expected
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            acc, t_last = fn(scfg, params, *samples)

    def collect():
        with _span("repro.sweep.fetch"):
            acc_h = jax.tree.map(lambda x: np.asarray(x)[:C], acc)
            t_h = np.asarray(t_last)[:C]
        if not prestamped:
            check_coverage(t_h, sim_rows, stamped=False)
        elif coverage is not None:
            coverage()
        if acc_h["overflow"].sum() > 0:
            raise RuntimeError(
                "instance-pool overflow during sweep; raise Scenario.slots"
            )
        with _span("repro.sweep.assemble"):
            n_cells = C // R
            cell = jax.tree.map(
                lambda x: x.reshape((n_cells, R) + x.shape[1:]), acc_h
            )
            bounds = np.asarray(wb, np.float64) if wb else None
            widths = np.diff(bounds) if wb else None
            summaries = []
            w_cold = np.zeros((n_cells, W)) if W else None
            w_arr = np.zeros((n_cells, W)) if W else None
            w_inst = np.zeros((n_cells, W)) if W else None
            for c in range(n_cells):
                row = c * R
                windows = None
                if W:
                    windows = WindowedMetrics(
                        bounds=bounds,
                        n_cold=cell["w_cold"][c],
                        n_warm=cell["w_warm"][c],
                        n_arrivals=cell["w_arrivals"][c],
                        time_running=cell["w_run_t"][c],
                        time_idle=cell["w_idle_t"][c],
                        n_fail=cell["w_fail"][c] if scfg.reliability else None,
                    )
                    w_cold[c] = windows.cold_start_prob
                    w_arr[c] = windows.n_arrivals.mean(axis=0)
                    w_inst[c] = (
                        windows.time_running + windows.time_idle
                    ).mean(axis=0) / widths
                rely_kw = {}
                if scfg.reliability:
                    rely_kw = dict(
                        n_timeout=cell["n_timeout"][c],
                        n_fail=cell["n_fail"][c],
                        n_retry=cell["n_retry"][c],
                        n_abandon=cell["n_abandon"][c],
                    )
                if scfg.crashes or scfg.cap_steps:
                    rely_kw.update(
                        n_crash=cell["n_crash"][c],
                        n_evict=cell["n_evict"][c],
                        n_interrupt=cell["n_interrupt"][c],
                    )
                summaries.append(
                    SimulationSummary(
                        n_cold=cell["n_cold"][c],
                        n_warm=cell["n_warm"][c],
                        n_reject=cell["n_reject"][c],
                        time_running=cell["time_running"][c],
                        time_idle=cell["time_idle"][c],
                        sum_cold_resp=cell["sum_cold_resp"][c],
                        sum_warm_resp=cell["sum_warm_resp"][c],
                        lifespan_sum=cell["lifespan_sum"][c],
                        lifespan_count=cell["lifespan_count"][c],
                        measured_time=float(sim_rows[row] - skip_rows[row]),
                        histogram=cell["hist"][c]
                        if scfg.track_histogram
                        else None,
                        overflow=cell["overflow"][c],
                        windows=windows,
                        **rely_kw,
                    )
                )
            win = (
                dict(cold=w_cold, arrivals=w_arr, instances=w_inst) if W else None
            )
            return summaries, win

    return collect


@functools.lru_cache(maxsize=None)
def _block_sharded_executable(backend: str, mesh, kw_items: tuple):
    """The jitted shard_map wrapper for a block backend's row launcher.

    Mirrors :func:`repro.core.simulator.sweep_executable`: a 1-D mesh
    (axis ``"grid"``) splits the flattened row axis, each device runs the
    same row launcher on its contiguous slice (rows are independent, so
    per-cell results are bitwise-identical to the unsharded launch).  The
    caller pads the row axis so every shard is whole replica blocks of
    the kernel (:func:`_block_launch`).  Cached per (backend, mesh,
    static launch config); traces pinned by
    ``TRACE_COUNTS["sweep_block_sharded"]``.
    """
    from jax.sharding import PartitionSpec

    bspec = resolve_backend(backend)
    kw = dict(kw_items)
    windowed = kw.pop("windowed")
    spec = PartitionSpec("grid")

    def body(*arrays):
        if windowed:
            *main, wb = arrays
            return bspec.launch(*main, window_bounds=wb, **kw)
        return bspec.launch(*arrays, **kw)

    def fn(*arrays):
        TRACE_COUNTS["sweep_block_sharded"] += 1
        # check_vma=False: the row-parallel body has no collectives, and
        # pallas_call has no varying-axes rule under shard_map
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec,) * len(arrays),
            out_specs=spec,
            check_vma=False,
        )(*arrays)

    return jax.jit(fn)


def _block_launch(
    scn, t_exp, t_end, skip, dts, warms, colds, bspec, kw, block_k=512,
    plan=None, window_rows=None, t_to_rows=None, pf_rows=None, extras=(),
    fused=None, crash_rate_rows=None, crash_u=None, cap_edges=None,
    cap_values=None,
):
    """Shared f32 block-engine launch: prepare the per-row f32 state and
    sample buffers and hand them to the registered backend's row launcher
    (``BackendSpec.launch`` — the Pallas kernel's padded grid, or the jnp
    ref mirror).

    ``t_exp``/``t_end``/``skip`` are per-row ``[C]`` vectors (all three are
    traced sweep axes); ``window_rows`` is the optional ``[C, W+1]`` traced
    window-boundary matrix (irregular grids welcome).  ``dts`` rows are
    gaps, or absolute times when ``kw['prestamped']``.  When ``plan`` asks
    for ``shard="grid"``, the row axis is padded with copies of row 0
    (sliced off after) so that every device's shard is whole replica
    blocks of the kernel, and the launch runs under
    :func:`_block_sharded_executable`.  The launch span carries the
    kernel's blocking (:func:`~repro.kernels.faas_event_step.sweep_layout`
    of one device's rows).  Returns the f64 accumulator ``[C, cols]``
    after the overflow guard.
    """
    # kernel imports stay local so the default scan backend keeps core
    # imports light; NEG is the kernel's dead-slot sentinel
    from repro.kernels.faas_event_step import (
        NEG as _F32_NEG,
        _pad_rows,
        sweep_layout,
    )

    if scn.routing != "newest":
        raise ValueError(
            "block backends implement newest-idle routing only; use "
            f"backend='scan' for routing={scn.routing!r}"
        )
    if fused is not None:
        C = len(np.asarray(t_exp))
        n_steps = int(fused["n_steps"])
    else:
        C, n_steps = dts.shape
        dts, warms, colds = (
            jnp.asarray(dts, jnp.float32),
            jnp.asarray(warms, jnp.float32),
            jnp.asarray(colds, jnp.float32),
        )
    as_rows = lambda x: jnp.broadcast_to(
        jnp.asarray(x, jnp.float32), (C,)
    )
    t_exp, t_end, skip = as_rows(t_exp), as_rows(t_end), as_rows(skip)
    M = scn.slots
    alive0 = jnp.zeros((C, M), jnp.float32)
    frozen = jnp.full((C, M), _F32_NEG, jnp.float32)
    t0 = jnp.zeros((C,), jnp.float32)
    args = (alive0, frozen, frozen, t0, t_exp, t_end, skip, dts, warms, colds)
    if window_rows is not None:
        window_rows = jnp.asarray(window_rows, jnp.float32)
    rely_kw = {}
    if t_to_rows is not None:
        rely_kw = dict(
            t_timeout=as_rows(t_to_rows),
            p_fail=as_rows(pf_rows),
        )
        if extras:
            # (fail_u,) without retries, (fail_u, is_first, child_pos) with
            ex = tuple(jnp.asarray(x, jnp.float32) for x in extras)
            rely_kw["fail_u"] = ex[0]
            if len(ex) == 3:
                rely_kw.update(is_first=ex[1], child_pos=ex[2])
    fault_kw = {}
    if crash_rate_rows is not None:
        fault_kw.update(
            crash_rate=as_rows(crash_rate_rows),
            crash_u=jnp.asarray(crash_u, jnp.float32),
        )
    if cap_edges is not None:
        fault_kw.update(
            cap_edges=jnp.asarray(cap_edges, jnp.float32),
            cap_values=jnp.asarray(cap_values, jnp.float32),
        )
    if fused is not None:
        # Execution.resolve() already rejects fused × shard='grid'; the
        # launcher returns (acc, t_final) — the kernel clock replaces the
        # host-side gap sum for the caller's coverage guard.
        if window_rows is not None:
            kw = dict(kw, window_bounds=window_rows)
        with _span("repro.sweep.launch", **sweep_layout(C, n_steps, block_k)):
            acc, t_last = bspec.launch(
                *args, fused=fused, block_k=block_k, **rely_kw, **kw
            )
        with _span("repro.sweep.fetch"):
            acc = np.asarray(acc, np.float64)
            t_last = np.asarray(t_last, np.float64)
        if acc[:, 7].sum() > 0:
            raise RuntimeError(
                "instance-pool overflow during sweep; raise Scenario.slots"
            )
        return acc, t_last
    if plan is not None and plan.shard == "grid":
        if rely_kw:
            raise ValueError(
                "reliability sweeps on block backends are single-device; "
                "drop shard='grid' or use backend='scan'"
            )
        if fault_kw:
            raise ValueError(
                "fault sweeps on block backends are single-device; "
                "drop shard='grid' or use backend='scan'"
            )
        mesh = plan.mesh()
        n_dev = int(mesh.devices.size)
        layout = sweep_layout(-(-C // n_dev), n_steps, block_k)
        pad = layout["rows"] * n_dev - C
        if window_rows is not None:
            args = args + (window_rows,)
        if pad:
            args = tuple(_pad_rows(x, pad) for x in args)
        fn = _block_sharded_executable(
            bspec.name,
            mesh,
            tuple(
                sorted(
                    {
                        **kw,
                        "block_k": block_k,
                        "windowed": window_rows is not None,
                    }.items()
                )
            ),
        )
        with _span("repro.sweep.launch", **layout):
            out = fn(*args)
        with _span("repro.sweep.fetch"):
            acc = np.asarray(out, np.float64)[:C]
    else:
        launch_kw = dict(kw, block_k=block_k, **rely_kw, **fault_kw)
        if window_rows is not None:
            launch_kw["window_bounds"] = window_rows
        with _span("repro.sweep.launch", **sweep_layout(C, n_steps, block_k)):
            out = bspec.launch(*args, **launch_kw)
        with _span("repro.sweep.fetch"):
            acc = np.asarray(out, np.float64)
    if acc[:, 7].sum() > 0:
        raise RuntimeError(
            "instance-pool overflow during sweep; raise Scenario.slots"
        )
    return acc


def _block_cells(
    scn_s, thr_rows, sim_rows, skip_rows, samples, R, prestamped, bspec, plan,
    rely_rows=None, fused=None, fault_rows=None, coverage=None,
):
    """One f32 block-engine launch → per-cell summaries.

    Windowed metrics run in-kernel (irregular grids included, the window
    boundaries being traced rows) and produce full per-cell
    :class:`WindowedMetrics` — counts *and* the per-window ∫running/∫idle
    instance-time integrals — exactly like the f64 scan path.  The
    coverage guard runs before the launch: ``coverage`` (batched staging's
    check of the coverage it reduced on the device) where given, else on
    the staged gaps summed on the host.
    """
    from repro.core.simulator import (
        SimulationSummary,
        WindowedMetrics,
        check_coverage,
    )
    from repro.kernels.faas_event_step import (
        ACC_COLS,
        FAULT_COLS,
        RELY_COLS,
        WINDOW_COLS,
    )

    if scn_s.track_histogram:
        raise ValueError("histograms need the f64 scan backend")
    rel = scn_s.reliability
    fr = fault_rows or {}
    crash_u = None
    if fused is not None:
        dts = warms = colds = None
        extras = ()
        n_draws = int(fused["n_steps"])
    else:
        dts, warms, colds = samples[:3]
        extras = tuple(samples[3:])
        if fr.get("crashes"):
            # the crash uniforms ride the sample tuple after the rely
            # extras (same order the scan consumes)
            crash_u, extras = extras[-1], extras[:-1]
        n_draws = dts.shape[1]
        if coverage is not None:
            with _span("repro.sweep.guard"):
                coverage()
        elif not prestamped:
            # Coverage guard on the REAL draws (before any padding): every
            # row's arrivals must reach its horizon, else the grid would be
            # silently truncated.  f64 sum of the f32 gaps — the padded
            # kernel clock cannot be used for this check.  (Fused rows are
            # guarded on the kernel's own final clock after the launch.)
            with _span("repro.sweep.guard"):
                covered = np.asarray(dts, np.float64).sum(axis=1)
            check_coverage(covered, sim_rows, stamped=False)
    wb = scn_s.window_bounds
    W = len(wb) - 1 if wb else 0
    window_rows = None
    if W:
        bounds = np.asarray(wb, np.float64)
        widths = np.diff(bounds)
        window_rows = np.tile(bounds, (len(thr_rows), 1))
    kw = dict(
        max_concurrency=scn_s.max_concurrency,
        prestamped=prestamped,
        n_windows=W,
    )
    rr = rely_rows or {}
    acc = _block_launch(
        scn_s, thr_rows, sim_rows, skip_rows, dts, warms, colds, bspec, kw,
        block_k=plan.resolved_block_k(n_draws),
        plan=plan,
        window_rows=window_rows,
        t_to_rows=rr.get("t_timeout") if rel is not None else None,
        pf_rows=rr.get("p_fail") if rel is not None else None,
        extras=extras,
        fused=fused,
        crash_rate_rows=fr.get("crash_rate"),
        crash_u=crash_u,
        cap_edges=fr.get("cap_edges"),
        cap_values=fr.get("cap_values"),
    )
    if fused is not None:
        acc, t_last = acc
        if (t_last < sim_rows).any():
            raise RuntimeError(
                "fused arrival stream ended before sim_time "
                f"(min final t {t_last.min():.1f}); pass a larger `steps`"
            )
    with _span("repro.sweep.assemble"):
        n_cells = len(thr_rows) // R
        fault_on = bool(fr)
        cols = (
            ACC_COLS
            + WINDOW_COLS * W
            + (RELY_COLS if rel is not None else 0)
            + (FAULT_COLS if fault_on else 0)
        )
        cell = acc.reshape(n_cells, R, cols)
        A = ACC_COLS
        RB = ACC_COLS + WINDOW_COLS * W  # reliability cols, then fault cols
        FB = RB + (RELY_COLS if rel is not None else 0)
        zeros = lambda: np.zeros((R,))
        summaries = []
        w_cold = np.zeros((n_cells, W)) if W else None
        w_arr = np.zeros((n_cells, W)) if W else None
        w_inst = np.zeros((n_cells, W)) if W else None
        for c in range(n_cells):
            row = c * R
            windows = None
            if W:
                cold_c = cell[c, :, A : A + W]
                served_c = cell[c, :, A + W : A + 2 * W]
                windows = WindowedMetrics(
                    bounds=bounds,
                    n_cold=cold_c,
                    n_warm=served_c - cold_c,
                    n_arrivals=cell[c, :, A + 2 * W : A + 3 * W],
                    time_running=cell[c, :, A + 3 * W : A + 4 * W],
                    time_idle=cell[c, :, A + 4 * W : A + 5 * W],
                )
                w_cold[c] = windows.cold_start_prob
                w_arr[c] = windows.n_arrivals.mean(axis=0)
                w_inst[c] = (
                    windows.time_running + windows.time_idle
                ).mean(axis=0) / widths
            rely_kw = {}
            if rel is not None:
                rely_kw = dict(
                    n_timeout=cell[c, :, RB + 0],
                    n_fail=cell[c, :, RB + 1],
                    n_retry=cell[c, :, RB + 2],
                    n_abandon=cell[c, :, RB + 3],
                )
            if fault_on:
                rely_kw.update(
                    n_crash=cell[c, :, FB + 0],
                    n_evict=cell[c, :, FB + 1],
                    n_interrupt=cell[c, :, FB + 2],
                )
            summaries.append(
                SimulationSummary(
                    n_cold=cell[c, :, 0],
                    n_warm=cell[c, :, 1],
                    n_reject=cell[c, :, 2],
                    time_running=cell[c, :, 3],
                    time_idle=cell[c, :, 4],
                    sum_cold_resp=cell[c, :, 5],
                    sum_warm_resp=cell[c, :, 6],
                    lifespan_sum=zeros(),
                    lifespan_count=zeros(),
                    measured_time=float(sim_rows[row] - skip_rows[row]),
                    overflow=cell[c, :, 7],
                    windows=windows,
                    **rely_kw,
                )
            )
        win = (
            dict(cold=w_cold, arrivals=w_arr, instances=w_inst) if W else None
        )
        return summaries, win
