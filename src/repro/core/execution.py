"""Execution plans and the engine/backend registry (DESIGN.md §9).

The Scenario API separates *what to simulate* (a frozen :class:`Scenario`)
from *how to execute it*.  This module owns the "how": a frozen
:class:`Execution` plan naming the engine (simulation semantics), the
backend (execution substrate), device placement and grid sharding,
precision expectations, the block-kernel chunk size and buffer donation —
resolved against a registry the engine/backend modules populate:

* ``repro.core.simulator`` registers engine ``"scan"`` and backend
  ``"scan"`` (f64 ``lax.scan``, exact);
* ``repro.core.temporal`` / ``repro.core.par_simulator`` register engines
  ``"temporal"`` / ``"par"`` — declaring ``backends=("scan",)`` instead of
  scattering ``if backend != "scan"`` checks;
* ``repro.kernels.ref`` / ``repro.kernels.faas_event_step`` register the
  f32 block backends ``"ref"`` / ``"pallas"`` (each contributes its row
  launcher).

Registration happens at module import; the registry lazy-imports the
providing module on first resolution (``_PROVIDERS``), so the default
scan path never pays the kernel/model-stack import.  Unknown names raise
with the full registered list; capability violations (a backend an engine
cannot drive, a non-shardable backend under ``shard="grid"``) raise with
the declared capability.

Sharded sweeps: ``Execution(devices=..., shard="grid")`` makes
``scenario.sweep`` split the single flattened grid axis across a 1-D
device mesh with ``shard_map`` (axis name ``"grid"``), padding the axis
to a multiple of the device count.  Rows are independent, so the sharded
sweep is bitwise-equal per cell to the single-device one.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import pathlib
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import numpy as np


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """A registered simulation engine (the semantics axis).

    ``run(scn, key, plan, *, replicas, steps, grid, initial_instances)``
    returns ``(summary, temporal_or_None)``.  ``backends`` declares which
    execution substrates the engine can drive — the registry enforces it
    so engines never need per-call-site backend checks.  ``sweepable``
    declares whether :func:`repro.core.scenario.sweep` can batch this
    engine onto the flattened grid axis; the grid machinery itself lives
    in the built-in ``scan`` engine, so today only it may declare this
    (``sweep`` rejects other sweepable engines loudly instead of running
    scan semantics under their name).
    """

    name: str
    run: Callable[..., Any]
    backends: Tuple[str, ...]
    sweepable: bool = False
    # backends on which this engine produces windowed (per-time-grid)
    # metrics — the capability-matrix column; a declaration, not a check
    windowed_backends: Tuple[str, ...] = ()
    # backends on which this engine serves the reliability layer
    # (timeouts / failures / retries, DESIGN.md §11)
    reliability_backends: Tuple[str, ...] = ()
    # backends on which this engine can generate draws inline from a
    # DrawPlan (``Execution(draws="fused")``, DESIGN.md §12) instead of
    # consuming host-staged [C, K] sample buffers
    fused_backends: Tuple[str, ...] = ()
    # backends on which this engine serves the multi-function fleet
    # coupling (shared cluster capacity + per-function pools,
    # DESIGN.md §13) — consumed by repro.core.fleet
    fleet_backends: Tuple[str, ...] = ()
    # backends on which this engine serves platform fault injection
    # (instance crashes + capacity churn, DESIGN.md §15)
    faults_backends: Tuple[str, ...] = ()
    description: str = ""


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """A registered execution substrate (the how-to-run axis).

    ``kind="native"`` backends are executed directly by the engine
    (the f64 scan); ``kind="block"`` backends provide per-engine row
    launchers in ``launchers`` — the f32 row-kernel entry points the
    engines call with prepared ``[C, ...]`` row buffers (the pool-state
    engines share one launcher; the par engine's ``finish[M, c]`` state
    has its own).  ``shardable`` declares support for
    ``Execution(shard="grid")``; ``precision`` is the substrate's compute
    dtype, checked against ``Execution.precision`` when given.
    """

    name: str
    precision: str  # "f64" | "f32"
    kind: str = "block"  # "native" | "block"
    shardable: bool = False
    launchers: Any = dataclasses.field(default_factory=dict)  # engine -> fn
    description: str = ""

    @property
    def launch(self) -> Optional[Callable[..., Any]]:
        """The steady-state (scan-engine) row launcher — the common case."""
        return self.launchers.get("scan")

    def launch_for(self, engine: str) -> Callable[..., Any]:
        """The row launcher serving ``engine``; raises with the served
        list when the backend has none for it."""
        fn = self.launchers.get(engine)
        if fn is None:
            raise ValueError(
                f"backend {self.name!r} has no row launcher for engine "
                f"{engine!r}; launchers: {sorted(self.launchers)}"
            )
        return fn


_ENGINES: dict = {}
_BACKENDS: dict = {}

# name -> module that registers it on import (kept lazy so the default
# scan path never imports the kernel/model stack)
_PROVIDERS = {
    ("engine", "scan"): "repro.core.simulator",
    ("engine", "temporal"): "repro.core.temporal",
    ("engine", "par"): "repro.core.par_simulator",
    ("backend", "scan"): "repro.core.simulator",
    ("backend", "ref"): "repro.kernels.ref",
    ("backend", "pallas"): "repro.kernels.faas_event_step",
}


def register_engine(
    name: str,
    *,
    backends: Sequence[str],
    sweepable: bool = False,
    windowed_backends: Sequence[str] = (),
    reliability_backends: Sequence[str] = (),
    fused_backends: Sequence[str] = (),
    fleet_backends: Sequence[str] = (),
    faults_backends: Sequence[str] = (),
    description: str = "",
):
    """Decorator: register ``fn`` as engine ``name``'s run entry point."""

    def deco(fn):
        _ENGINES[name] = EngineSpec(
            name=name,
            run=fn,
            backends=tuple(backends),
            sweepable=sweepable,
            windowed_backends=tuple(windowed_backends),
            reliability_backends=tuple(reliability_backends),
            fused_backends=tuple(fused_backends),
            fleet_backends=tuple(fleet_backends),
            faults_backends=tuple(faults_backends),
            description=description,
        )
        return fn

    return deco


def register_backend(
    name: str,
    *,
    precision: Optional[str] = None,
    kind: str = "block",
    shardable: bool = False,
    description: str = "",
    engines: Sequence[str] = ("scan",),
):
    """Register backend ``name``.  Usable three ways: a plain call with
    ``precision`` registers a ``kind="native"``-style backend with no
    launcher; applying the returned decorator to a function registers it
    as the backend's block row launcher for every engine in ``engines``;
    and a later call *without* ``precision`` augments an already-declared
    backend with additional per-engine launchers (e.g. the par platform's
    ``finish[M, c]`` kernel) without re-stating its metadata."""
    if precision is None:
        if name not in _BACKENDS:
            raise ValueError(
                f"backend {name!r} is not declared yet; pass precision= "
                "on the first registration"
            )
    else:
        _BACKENDS[name] = BackendSpec(
            name=name,
            precision=precision,
            kind=kind,
            shardable=shardable,
            description=description,
        )

    def deco(fn):
        spec = _BACKENDS[name]
        _BACKENDS[name] = dataclasses.replace(
            spec, launchers={**spec.launchers, **{e: fn for e in engines}}
        )
        return fn

    return deco


def _materialize(kind: str, name: Optional[str] = None) -> None:
    """Import the module(s) that register the requested (or all) names.

    Importing the *specifically requested* name is strict: a provider
    that fails while importing (broken transitive dep) is a real bug and
    must not be masked as "unknown engine/backend" — only the provider
    module itself being absent hides its name.  The ``name=None`` pass
    only builds the registered-names listing for error messages and
    introspection, so there every unimportable provider just drops out.
    """
    for (k, n), mod in _PROVIDERS.items():
        if k == kind and (name is None or n == name):
            try:
                importlib.import_module(mod)
            except ImportError as e:
                if (
                    name is not None
                    and e.name != mod
                    and not mod.startswith(f"{e.name}.")
                ):
                    raise


def resolve_engine(name: str) -> EngineSpec:
    if name not in _ENGINES:
        _materialize("engine", name)
    if name not in _ENGINES:
        _materialize("engine")  # the error should list everything known
        raise ValueError(
            f"unknown engine {name!r}; registered engines: "
            f"{sorted(_ENGINES)}"
        )
    return _ENGINES[name]


def resolve_backend(name: str) -> BackendSpec:
    if name not in _BACKENDS:
        _materialize("backend", name)
    if name not in _BACKENDS:
        _materialize("backend")
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{sorted(_BACKENDS)}"
        )
    return _BACKENDS[name]


def registered_engines() -> dict:
    """Snapshot of every registered engine spec (imports all providers)."""
    _materialize("engine")
    return dict(sorted(_ENGINES.items()))


def registered_backends() -> dict:
    """Snapshot of every registered backend spec (imports all providers)."""
    _materialize("backend")
    return dict(sorted(_BACKENDS.items()))


@dataclasses.dataclass(frozen=True)
class Execution:
    """One frozen execution plan: how a scenario (grid) actually runs.

    * ``engine`` — simulation semantics (``"scan"`` steady-state,
      ``"temporal"`` transient, ``"par"`` concurrency-value).
    * ``backend`` — execution substrate (``"scan"`` f64 exact,
      ``"pallas"``/``"ref"`` f32 block engine).
    * ``devices`` — placement for sharded sweeps: ``None`` (all local
      devices), an ``int`` (first N local devices) or an explicit
      sequence of ``jax.Device``.
    * ``shard`` — ``"grid"`` splits the flattened sweep axis over a 1-D
      mesh of ``devices`` via ``shard_map`` (padding the axis to a
      multiple of the device count; bitwise-equal per cell).  ``None``
      runs single-device.
    * ``precision`` — expected compute dtype; when set it is validated
      against the backend's declared precision (the plan fails loudly
      instead of silently computing in the wrong domain).
    * ``block_k`` — arrival-chunk size for the Pallas block kernel;
      ``None`` (the default) auto-selects from the stream length and a
      VMEM budget at launch time (:meth:`resolved_block_k`), and the
      chosen value is exposed on the result's resolved plan.
    * ``draws`` — how sample streams reach the engine.  ``"staged"``
      (the default) pre-draws ``[C, K]`` buffers host-side and streams
      them through the engine — bitwise-stable against earlier releases.
      ``"fused"`` lowers the scenario's processes to a :mod:`drawplan`
      and generates every draw *inline* (counter-based threefry inside
      the scan body / kernel row-chunk), eliminating the O(C·K) HBM
      sample buffers; only engines declaring the backend in
      ``fused_backends`` accept it, and the resolved value is exposed on
      the result's plan.
    * ``donate`` — donate the grid's sample buffers into the sweep call
      (they dominate the allocation and are dead afterwards); turn off
      to reuse sample arrays across calls.  Applies to the f64 scan
      backend only: the block launchers stage their own f32 copies of
      the samples, so there is nothing of the caller's to donate there.
    """

    engine: str = "scan"
    backend: str = "scan"
    devices: Optional[Union[int, Tuple[Any, ...]]] = None
    shard: Optional[str] = None
    precision: Optional[str] = None
    block_k: Optional[int] = None
    draws: Optional[str] = None
    donate: bool = True

    def __post_init__(self):
        if self.draws not in (None, "staged", "fused"):
            raise ValueError(
                f"unknown draws mode {self.draws!r}; supported: 'staged' "
                "(host-built sample buffers) and 'fused' (inline "
                "counter-based generation from a DrawPlan)"
            )
        if self.shard not in (None, "grid"):
            raise ValueError(
                f"unknown shard spec {self.shard!r}; supported: 'grid' "
                "(split the flattened sweep axis across devices)"
            )
        if self.precision not in (None, "f32", "f64"):
            raise ValueError(
                f"unknown precision {self.precision!r}; supported: "
                "'f32', 'f64'"
            )
        if self.block_k is not None and self.block_k < 1:
            raise ValueError("block_k must be >= 1")
        d = self.devices
        if d is not None and not isinstance(d, int):
            d = tuple(d)
            if not d:
                raise ValueError(
                    "devices sequence is empty (e.g. a platform filter "
                    "that matched nothing); pass None for all local devices"
                )
            object.__setattr__(self, "devices", d)
        elif isinstance(d, int) and d < 1:
            raise ValueError("devices count must be >= 1")

    # ---- registry resolution -------------------------------------------
    def resolve(self) -> Tuple[EngineSpec, BackendSpec]:
        """Look up and validate the (engine, backend) pair.

        Raises with the registered list on unknown names and with the
        declared capability on invalid combinations.
        """
        espec = resolve_engine(self.engine)
        bspec = resolve_backend(self.backend)
        if self.backend not in espec.backends:
            raise ValueError(
                f"engine {self.engine!r} supports backends "
                f"{espec.backends}; got backend {self.backend!r}"
            )
        if (
            self.shard == "grid"
            and self.precision == "f64"
            and bspec.precision == "f32"
        ):
            # the generic precision mismatch below would fire too, but a
            # sharded-f64 ask deserves the full answer: the f64 domain IS
            # shardable — on the scan backend
            raise ValueError(
                f"shard='grid' with precision='f64' cannot run on backend "
                f"{self.backend!r} (an f32 block backend); sharded f64 "
                "sweeps run on backend='scan' — switch to it, or drop "
                "precision='f64' to keep the f32 block path"
            )
        if self.precision is not None and self.precision != bspec.precision:
            raise ValueError(
                f"backend {self.backend!r} computes in {bspec.precision}; "
                f"requested precision {self.precision!r} (drop precision= "
                "or pick a backend in that domain)"
            )
        if self.resolved_draws == "fused":
            if self.backend not in espec.fused_backends:
                raise ValueError(
                    f"engine {self.engine!r} cannot generate fused draws on "
                    f"backend {self.backend!r}; fused-capable backends: "
                    f"{espec.fused_backends or '()'} (drop draws='fused' to "
                    "keep the staged pipeline)"
                )
            if self.shard == "grid":
                raise ValueError(
                    "draws='fused' does not support shard='grid' yet; the "
                    "sharded sweep executable consumes staged sample "
                    "buffers — drop shard= or use draws='staged'"
                )
        if self.shard == "grid" and not bspec.shardable:
            shardable = sorted(
                n for n, s in registered_backends().items() if s.shardable
            )
            raise ValueError(
                f"backend {self.backend!r} does not support shard='grid'; "
                f"shardable backends: {shardable}"
            )
        if self.devices is not None and self.shard is None:
            # device placement only takes effect through grid sharding —
            # silently running single-device would make the plan lie
            raise ValueError(
                "devices= is set but shard is None, so the plan would run "
                "single-device; add shard='grid' (or drop devices=)"
            )
        return espec, bspec

    # ---- device placement ----------------------------------------------
    def resolved_devices(self) -> tuple:
        """The concrete device tuple this plan runs on."""
        import jax

        if self.devices is None:
            return tuple(jax.devices())
        if isinstance(self.devices, int):
            avail = jax.devices()
            if self.devices > len(avail):
                raise ValueError(
                    f"Execution.devices={self.devices} but only "
                    f"{len(avail)} devices are visible"
                )
            return tuple(avail[: self.devices])
        return tuple(self.devices)

    @property
    def n_devices(self) -> int:
        return len(self.resolved_devices())

    def mesh(self):
        """1-D device mesh over ``resolved_devices()`` (axis ``"grid"``)."""
        from jax.sharding import Mesh

        return Mesh(np.asarray(self.resolved_devices()), ("grid",))

    # ---- draw generation mode ------------------------------------------
    @property
    def resolved_draws(self) -> str:
        """The concrete draw mode: an unset ``draws`` means staged."""
        return self.draws or "staged"

    # ---- block-kernel chunking -----------------------------------------
    def resolved_block_k(self, n_steps: int) -> int:
        """The concrete event-chunk size for an ``n_steps``-long stream of
        the single-function block kernel (rows on lanes, events on
        sublanes; DESIGN.md §5).

        An explicit ``block_k`` is honoured (clamped to the stream length,
        rounded up to whole :data:`SUBLANE` tiles).  ``block_k=None``
        splits the stream into the fewest chunks that fit
        :data:`BLOCK_K_VMEM_BUDGET` (three ``[block_k, 128]`` f32 sample
        blocks) and balances them, so the padded stream is at most
        ``n_steps`` plus 8 events a chunk.  The launcher pads ``K`` up to
        a ``block_k`` multiple either way, so every choice is
        semantics-free; engines report the chosen value on the result's
        resolved plan.
        """
        if self.block_k is not None:
            return sublane_chunk(self.block_k, n_steps)
        n = max(int(n_steps), 1)
        chunks = -(-n // _AUTO_SUBLANE_BLOCK_K_MAX)
        return sublane_chunk(-(-n // chunks), n)

    def resolved_lane_block_k(self, n_steps: int) -> int:
        """The arrival-chunk size of the lane-major kernels (fleet, par),
        whose ``[8, block_k]`` sample blocks are whole 128-lane tiles:
        ``block_k`` (or the VMEM ceiling :data:`_AUTO_BLOCK_K_MAX`)
        clamped to the stream and rounded up by :func:`lane_chunk`."""
        return lane_chunk(
            _AUTO_BLOCK_K_MAX if self.block_k is None else self.block_k,
            n_steps,
        )


# TPU vreg tile: 8 sublanes × 128 lanes of 32-bit words
LANE = 128
SUBLANE = 8

# Auto block_k VMEM budget: bytes allowed for the three f32 sample blocks
# of one replica block.  The single-function kernel's blocks are
# [block_k, 128] (1 MiB / (3 · 128 · 4 B) = 682 events, rounded down to
# whole sublane tiles); the lane-major kernels' are [8, block_k] (10922
# columns, rounded down to whole lane tiles).
BLOCK_K_VMEM_BUDGET = 1 << 20
_AUTO_SUBLANE_BLOCK_K_MAX = (
    BLOCK_K_VMEM_BUDGET // (3 * LANE * 4) // SUBLANE * SUBLANE
)
_AUTO_BLOCK_K_MAX = (BLOCK_K_VMEM_BUDGET // (3 * 8 * 4)) // LANE * LANE


def sublane_chunk(block_k: int, n_steps: int) -> int:
    """``block_k`` clamped to an ``n_steps``-long stream and rounded up to
    whole :data:`SUBLANE` tiles — the single-function kernel reads each
    event's samples as one sublane row; the launcher's inert padding
    absorbs the extra events."""
    k = min(int(block_k), max(int(n_steps), 1))
    return -(-k // SUBLANE) * SUBLANE


def lane_chunk(block_k: int, n_steps: int) -> int:
    """``block_k`` clamped to an ``n_steps``-long stream and rounded up to
    whole :data:`LANE` tiles — the lane-major Pallas kernels read each
    event's samples from its aligned lane tile; the launchers' inert
    padding absorbs the extra events."""
    k = min(int(block_k), max(int(n_steps), 1))
    return -(-k // LANE) * LANE


_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself, and no other directory is set here); otherwise the fixed
    ``<repo>/.jax_cache`` — fixed because the path is part of what a
    later process must find again.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def capability_markdown() -> str:
    """The engine × backend capability matrix as a markdown table,
    generated from the live registry (README "Capability matrix" section;
    a test pins the README copy against this output)."""
    engines = registered_engines()
    backends = registered_backends()
    lines = [
        "| engine | backend | precision | `shard=\"grid\"` | windowed metrics | reliability | draws | fleet | faults |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for ename, espec in engines.items():
        for bname, bspec in backends.items():
            if bname not in espec.backends:
                continue
            sweepable = espec.sweepable
            fused = bname in espec.fused_backends
            lines.append(
                f"| `{ename}` | `{bname}` | {bspec.precision} | "
                f"{'✓' if sweepable and bspec.shardable else '—'} | "
                f"{'✓' if bname in espec.windowed_backends else '—'} | "
                f"{'✓' if bname in espec.reliability_backends else '—'} | "
                f"{'staged+fused' if fused else 'staged'} | "
                f"{'✓' if bname in espec.fleet_backends else '—'} | "
                f"{'✓' if bname in espec.faults_backends else '—'} |"
            )
    return "\n".join(lines)


def plan_of(
    execution: Optional[Execution],
    engine: Optional[str] = None,
    backend: Optional[str] = None,
) -> Execution:
    """The compatibility seam: merge an optional plan with the legacy
    ``engine=``/``backend=`` string kwargs (kwargs win, so pre-plan call
    sites keep working unchanged)."""
    plan = execution if execution is not None else Execution()
    if not isinstance(plan, Execution):
        raise TypeError(
            f"execution must be an Execution plan, got {type(plan).__name__}"
        )
    changes = {}
    if engine is not None:
        changes["engine"] = engine
    if backend is not None:
        changes["backend"] = backend
    return dataclasses.replace(plan, **changes) if changes else plan
