"""The benchmark's own copy of the draws the program stages for a request.

Regenerates, from a request's key, exactly the sample stacks the
program's staged-draw path feeds its engines: the same ``jax.random``
calls in the same order on the same device, so the reference simulates
the very draws the timed path consumed.  A change to the program that
moved a single draw would show as a gap against the reference.

Conventions copied (and frozen here): a sweep walks one chained
``split`` per draw cell in grid order; each cell splits its key in three
(arrivals, warm, cold); a sample is drawn in float64, scaled, cast to
float32 and clamped at 1e-9; NHPP arrivals thin a homogeneous candidate stream and sort the accepted
times to the front (rejected ones at 1e30).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_TIME = 1e30
_EPS = 1e-9


def steps_needed(sim_time: float, mean_gap: float) -> int:
    """Arrival-stream length covering ``sim_time``: mean + 6 sigma + 16."""
    n = sim_time / mean_gap
    return int(n + 6.0 * np.sqrt(max(n, 1.0)) + 16)


def _finish(x):
    return jnp.maximum(x.astype(jnp.float32), _EPS)


def sample(dist: dict, key, shape):
    """One service or inter-arrival stack from a distribution entry of a
    configuration: ``{"dist": "exp", "rate": r}``, ``{"dist": "gauss",
    "mu": m, "sigma": s}`` or ``{"dist": "lognorm", "mu": m, "sigma": s}``."""
    kind = dist["dist"]
    if kind == "exp":
        return _finish(jax.random.exponential(key, shape, jnp.float64) / dist["rate"])
    if kind == "gauss":
        return _finish(dist["mu"] + dist["sigma"] * jax.random.normal(key, shape, jnp.float64))
    if kind == "lognorm":
        return _finish(jnp.exp(dist["mu"] + dist["sigma"] * jax.random.normal(key, shape, jnp.float64)))
    raise ValueError(f"unknown distribution {kind!r}")


def mean_gap(dist: dict) -> float:
    if dist["dist"] == "exp":
        return 1.0 / dist["rate"]
    raise ValueError(f"arrivals must be exponential, got {dist['dist']!r}")


def piecewise_rate(edges, rates, t):
    idx = jnp.searchsorted(jnp.asarray(edges, jnp.float64), jnp.asarray(t, jnp.float64), side="right")
    return jnp.asarray(rates, jnp.float64)[idx]


def nhpp_times(key, shape, edges, rates):
    """Absolute arrival times of a piecewise-constant NHPP by thinning."""
    lam = float(max(rates))
    k_gap, k_acc = jax.random.split(key)
    gaps = jax.random.exponential(k_gap, shape, jnp.float64) / lam
    cand = jnp.cumsum(gaps.astype(jnp.float64), axis=-1)
    u = jax.random.uniform(k_acc, shape, jnp.float64)
    accept = u * lam <= piecewise_rate(edges, rates, cand)
    times = jnp.sort(jnp.where(accept, cand, PAD_TIME), axis=-1)
    return times, cand[..., -1]


def cell_keys(key, n_cells: int) -> list:
    """The chained per-cell keys of a sweep over ``n_cells`` draw cells."""
    out = []
    for _ in range(n_cells):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


def single_cell(key, arrival: dict, warm: dict, cold: dict, replicas: int, n: int):
    """``(arrival gaps, warm, cold)`` float32 ``[replicas, n]`` host arrays
    of one draw cell with stationary arrivals."""
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (replicas, n)
    return tuple(
        np.asarray(x)
        for x in (sample(arrival, k1, shape), sample(warm, k2, shape), sample(cold, k3, shape))
    )


def nhpp_cell(key, edges, rates, warm: dict, cold: dict, replicas: int, n: int, sim_time: float):
    """``(arrival times float64, warm, cold)`` of one NHPP draw cell."""
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (replicas, n)
    times, coverage = nhpp_times(k1, shape, edges, rates)
    if float(np.min(np.asarray(coverage))) < sim_time:
        raise RuntimeError("reference NHPP candidate stream ended before the horizon")
    return (
        np.asarray(times, np.float64),
        np.asarray(sample(warm, k2, shape)),
        np.asarray(sample(cold, k3, shape)),
    )


def rate_dist(rate: float) -> dict:
    return {"dist": "exp", "rate": float(rate)}


def exp_mean(mean: float) -> dict:
    """An exponential entry from its mean, as the program is given it:
    the rate ``1 / mean``."""
    return rate_dist(1.0 / mean)

