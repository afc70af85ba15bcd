"""DrawPlan: stateless per-draw sample generation (DESIGN.md §12).

The staged sampling pipeline materializes full ``[C, K]`` draw stacks in
HBM before a sweep launches; for large grids those buffers — not compute —
dominate memory traffic and cap the feasible grid size.  This module is
the fused alternative: every ``SimProcess`` that admits a closed-form
inverse-CDF (or Box–Muller) transform lowers to a frozen :class:`DrawSpec`
— a distribution id plus two traced parameters — and samples are generated
*inside* the simulation from a counter-based threefry-2x32 generator, so
the only per-row sample state is an 8-byte key pair.

Key schedule (mirrors the staged ``fold_in`` chain exactly):

* per-cell key: the chained ``key, sub = jax.random.split(key)`` walk of
  ``scenario.sweep`` (unchanged);
* per-stream key: ``k1, k2, k3 = jax.random.split(sub, 3)`` for
  (arrival, warm, cold) and ``fold_in(sub, 1016)`` for the failure stream
  — the same salts the staged path uses;
* per-replica key: ``fold_in(k_stream, r)``, exported as raw uint32 pairs
  via :func:`stream_row_keys`;
* per-event: the *counter* is the global event index, so draw ``i`` of a
  row is ``threefry2x32(key_hi, key_lo, i, 0)`` — stateless, chunkable at
  any block size, and identical between the Pallas kernel, the jnp ref
  mirror and the f64 scan body.

The threefry rotation network is hand-written in pure uint32 ``jnp`` ops
(no ``jax.random`` tracing machinery, no ``pltpu`` PRNG primitive) so the
*same function* runs inside a Pallas kernel body, the jnp reference and
the scan — bitwise-equal across all three by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# strictly-positive clamp, mirroring SimProcess.sample's _EPS
_EPS = 1e-9

# distribution ids a process can lower to (kernel-supported subset; "nhpp"
# is scan-engine only — thinning needs the profile's rate(t) at trace time)
FUSED_DISTS = ("exp", "det", "gauss", "weibull", "lognorm", "pareto", "nhpp")

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def _rotl(x, r: int):
    return (x << r) | (x >> (32 - r))


def threefry2x32(k0, k1, c0, c1):
    """20-round threefry-2x32 in pure uint32 jnp ops.

    All four operands are (broadcastable) uint32 arrays; returns the two
    output words.  Written without ``jax.random`` so the identical op
    sequence executes inside Pallas kernel bodies, the jnp ref mirror and
    the f64 scan — the bitwise-equality anchor of the fused draw path.
    """
    u32 = lambda v: jnp.asarray(v, jnp.uint32)
    k0, k1, c0, c1 = u32(k0), u32(k1), u32(c0), u32(c1)
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(_PARITY))
    x0, x1 = c0 + k0, c1 + k1
    for block in range(5):
        rots = _ROT_A if block % 2 == 0 else _ROT_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def uniform_from_bits(bits) -> Array:
    """[0, 1) f32 uniform from uint32 bits (mantissa-fill bit trick)."""
    mant = (jnp.asarray(bits, jnp.uint32) >> 9) | np.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(mant, jnp.float32) - jnp.float32(1.0)


def event_uniforms(k0, k1, idx):
    """The two [0,1) f32 uniforms of event ``idx`` under key ``(k0, k1)``.

    ``idx`` is the global event index (uint32 counter); the second word of
    the counter is 0 — streams are separated by *key*, not counter.
    """
    b0, b1 = threefry2x32(k0, k1, idx, jnp.uint32(0))
    return uniform_from_bits(b0), uniform_from_bits(b1)


def sample_dist(kind: str, u0, u1, p0, p1):
    """One inverse-CDF/Box–Muller sample in the dtype of ``p0``.

    ``u0``/``u1`` are [0,1) f32 uniforms (cast up when params are f64 —
    the f64 scan consumes the *same* uniform bits as the f32 kernels);
    the result is clamped strictly positive like ``SimProcess.sample``.
    """
    dtype = jnp.asarray(p0).dtype
    u0 = jnp.asarray(u0, dtype)
    u1 = jnp.asarray(u1, dtype)
    one = jnp.asarray(1.0, dtype)
    if kind == "exp":
        out = -jnp.log(one - u0) / p0
    elif kind == "det":
        out = jnp.broadcast_to(p0, jnp.shape(u0))
    elif kind == "gauss":
        z = _box_muller(u0, u1, dtype)
        out = p0 + p1 * z
    elif kind == "weibull":
        out = p1 * (-jnp.log(one - u0)) ** (one / p0)
    elif kind == "lognorm":
        z = _box_muller(u0, u1, dtype)
        out = jnp.exp(p0 + p1 * z)
    elif kind == "pareto":
        out = p1 / (one - u0) ** (one / p0)
    else:  # pragma: no cover - guarded by lowering
        raise ValueError(f"unknown fused distribution {kind!r}")
    return jnp.maximum(out, jnp.asarray(_EPS, dtype))


def _box_muller(u0, u1, dtype):
    two_pi = jnp.asarray(2.0 * np.pi, dtype)
    r = jnp.sqrt(-2.0 * jnp.log(jnp.asarray(1.0, dtype) - u0))
    return r * jnp.cos(two_pi * u1)


# ---------------------------------------------------------------------------
# Specs and lowering
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DrawSpec:
    """One stream's stateless generator spec: a static distribution id.

    The two distribution parameters are *traced* per-row values (so a
    (threshold × rate) grid shares one compile) and ride outside the spec;
    ``profile`` is only set for ``kind == "nhpp"`` (the scan engine
    evaluates ``profile.rate(t)`` inline for thinning acceptance).
    """

    kind: str
    profile: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class DrawPlan:
    """The frozen per-scenario fused-draw plan: one spec per stream.

    Hashable (a jit static argument); parameter values and key material
    are traced companions built by :func:`lower_scenario` /
    :func:`stream_row_keys`.
    """

    arrival: DrawSpec
    warm: DrawSpec
    cold: DrawSpec
    fail: bool = False  # reliability failure-uniform stream (salt 1016)

    @property
    def dists(self) -> Tuple[str, str, str]:
        return (self.arrival.kind, self.warm.kind, self.cold.kind)


def lower_scenario(scn) -> Tuple[DrawPlan, dict]:
    """Lower a Scenario's processes to a :class:`DrawPlan`.

    Returns ``(plan, params)`` where ``params`` maps stream name →
    ``(p0, p1)`` floats.  Raises ``ValueError`` (pointing at
    ``draws="staged"``) for processes with no closed-form per-event
    transform (MMPP, trace replay, empirical, gamma, custom, batch) and
    for retry policies (the attempt table is a host-side sort).
    """
    rel = scn.reliability
    if rel is not None and int(rel.retry.max_retries) > 0:
        raise ValueError(
            "fused draws cannot serve retry policies (the attempt table "
            "is sorted host-side); use draws='staged'"
        )
    spec_a, par_a = _lower_process(scn.arrival_process, "arrival")
    spec_w, par_w = _lower_process(scn.warm_service_process, "warm")
    spec_c, par_c = _lower_process(scn.cold_service_process, "cold")
    for name, spec in (("warm", spec_w), ("cold", spec_c)):
        if spec.kind == "nhpp":
            raise ValueError(
                f"{name} service process cannot be an arrival-time process"
            )
    plan = DrawPlan(
        arrival=spec_a, warm=spec_w, cold=spec_c, fail=rel is not None
    )
    return plan, {"arrival": par_a, "warm": par_w, "cold": par_c}


def _lower_process(p, stream: str) -> Tuple[DrawSpec, Tuple[float, float]]:
    fn = getattr(p, "draw_spec", None)
    if fn is None:
        raise ValueError(
            f"{type(p).__name__} ({stream} stream) does not lower to a "
            "fused DrawSpec; use draws='staged'"
        )
    try:
        kind, params = fn()
    except NotImplementedError as e:
        raise ValueError(
            f"{type(p).__name__} ({stream} stream) does not lower to a "
            f"fused DrawSpec ({e}); use draws='staged'"
        ) from None
    profile = getattr(p, "profile", None) if kind == "nhpp" else None
    p0, p1 = (tuple(params) + (0.0, 0.0))[:2]
    return DrawSpec(kind=kind, profile=profile), (float(p0), float(p1))


def lower_staged(cfgs) -> Optional[Tuple[Tuple[str, str, str], dict]]:
    """Lower a sweep's draw cells for the batched staging program.

    The staged path's counterpart of :func:`lower_scenario`: the same
    family dispatch, but every parameter is a traced value, the NHPP
    profile's steps included, so re-rated cells and re-fit profiles share
    one compile (``simulator.stage_draw_cells``).  Returns ``(kinds,
    params)``: the three streams' draw kinds, the same in every cell, and
    each cell's parameters as f64 arrays with a leading cell axis —
    ``arrival``/``warm``/``cold`` ``[D, 2]``, and for NHPP arrivals the
    profile's ``edges`` ``[D, E]`` and ``rates`` ``[D, E + 1]`` (the
    envelope is the arrival's first parameter; profiles with fewer steps
    pad with edges at +inf, which no candidate time reaches).  Returns
    ``None`` where a cell's processes do not all lower to a family with a
    raw draw in ``processes.RAW_DRAWS`` (NHPP arrivals over a step
    profile aside), or where the cells' families differ: such grids are
    staged cell by cell.
    """
    from repro.core.processes import RAW_DRAWS

    kinds, rows, steps = None, [], []
    for scn in cfgs:
        try:
            plan, par = lower_scenario(scn)
        except ValueError:
            return None
        k = plan.dists
        if kinds is not None and k != kinds:
            return None
        kinds = k
        if not (k[0] == "nhpp" or k[0] in RAW_DRAWS) or not (
            k[1] in RAW_DRAWS and k[2] in RAW_DRAWS
        ):
            return None
        if k[0] == "nhpp":
            try:
                steps.append(plan.arrival.profile.step_arrays())
            except NotImplementedError:
                return None
        rows.append([par[s] for s in ("arrival", "warm", "cold")])
    pars = np.asarray(rows, np.float64)  # [D, 3, 2]
    params = {s: pars[:, i] for i, s in enumerate(("arrival", "warm", "cold"))}
    if steps:
        e_max = max(len(e) for e, _ in steps)
        pad = lambda e: (0, e_max - len(e))
        params["edges"] = np.stack(
            [np.pad(e, pad(e), constant_values=np.inf) for e, _ in steps]
        )
        params["rates"] = np.stack(
            [np.pad(r, pad(e), mode="edge") for e, r in steps]
        )
    return kinds, params


# ---------------------------------------------------------------------------
# Key derivation (the staged fold_in chain, exported as raw uint32 pairs)
# ---------------------------------------------------------------------------

_FAIL_SALT = 1016  # == simulator._RELY_SALT_FAIL (pinned by tests)


def _key_bits(k) -> Array:
    """Raw uint32 key data from a typed PRNG key (or already-raw array)."""
    if jnp.issubdtype(jnp.asarray(k).dtype, jax.dtypes.prng_key):
        return jax.random.key_data(k)
    return jnp.asarray(k, jnp.uint32)


def stream_row_keys(key, replicas: int, *, fail: bool = False) -> dict:
    """Per-row uint32 key pairs for each stream of one draw cell.

    Mirrors ``draw_workload_samples``'s ``split(key, 3)`` schedule and the
    reliability layer's ``fold_in(key, 1016)`` failure salt, then folds in
    the replica index — so the fused stream family is anchored on the
    exact same key chain as the staged one.  Returns a dict mapping
    ``"arrival"``/``"warm"``/``"cold"`` (and ``"fail"`` when asked) to
    uint32 ``[replicas, 2]`` arrays.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    rows = jnp.arange(replicas)
    fold = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
    out = {
        "arrival": _key_bits(fold(k1, rows)),
        "warm": _key_bits(fold(k2, rows)),
        "cold": _key_bits(fold(k3, rows)),
    }
    if fail:
        kf = jax.random.fold_in(key, _FAIL_SALT)
        out["fail"] = _key_bits(fold(kf, rows))
    return out


# ---------------------------------------------------------------------------
# Host-side materialization (oracle/tests: the fused stream as arrays)
# ---------------------------------------------------------------------------


def materialize_stream(kind: str, keys, params, n: int, dtype=jnp.float32):
    """``[R, n]`` array of the fused stream's values — the exact numbers
    the fused engines generate inline, materialized for the pure-Python
    oracle and for stream-stability tests.

    ``keys`` is uint32 ``[R, 2]``; ``params`` is ``(p0, p1)`` per-row (or
    scalar) values.  Not used on any hot path — fused runs never build
    these buffers; this is the cross-validation window into the stream.
    """
    keys = jnp.asarray(keys, jnp.uint32)
    idx = jnp.arange(n, dtype=jnp.uint32)[None, :]
    u0, u1 = event_uniforms(keys[:, :1], keys[:, 1:2], idx)
    p0 = jnp.asarray(params[0], dtype)
    p1 = jnp.asarray(params[1], dtype)
    if jnp.ndim(p0):
        p0, p1 = p0[:, None], p1[:, None]
    if kind == "uniform":
        return jnp.asarray(u0, dtype)
    return sample_dist(kind, u0, u1, p0, p1)
