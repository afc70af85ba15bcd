"""Plain event-driven reference simulator for the benchmark's ``correct``.

A copy, kept with the benchmark, of the part of the program's host
oracle (``simulate_pyref``) that the benchmark's deployments use: exponential or Gaussian service, newest-first
warm routing, expire-before-route at equal instants, no faults, no
retries, no queues.  It imports nothing of the program, so a change there
cannot move the yardstick.

The simulator consumes one row of pre-drawn samples (the draws the
program's timed path consumed, regenerated by ``bench/staging.py``) and
walks the events one at a time: advance the clock, integrate the
instance-time integrals over the gap, expire, then route the arrival to
the newest idle instance, a cold start or a rejection.

``rnd`` rounds every time the simulation computes.  ``None`` keeps
Python's float64; the benchmark's control passes a rounding to a lower
precision (``rounding("float32")``, ``rounding("bfloat16")``) so the same
walk runs as a lower-precision engine would.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np

Rounding = Optional[Callable[[float], float]]


def rounding(precision: str) -> Rounding:
    """The rounding of a float64 to ``precision``; ``None`` for float64."""
    if precision == "float64":
        return None
    if precision == "float32":
        return lambda x: float(np.float32(x))
    if precision == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        return lambda x: float(bf16(x))
    raise ValueError(f"unknown precision {precision!r}")


def _same(x: float) -> float:
    return x


SINGLE_FIELDS = (
    "n_cold",
    "n_warm",
    "n_reject",
    "time_running",
    "time_idle",
    "sum_cold_resp",
    "sum_warm_resp",
)


def simulate(
    arrivals,
    warms,
    colds,
    expiration_threshold: float,
    max_concurrency: int,
    sim_time: float,
    skip_time: float,
    prestamped: bool,
    rnd: Rounding = None,
) -> dict:
    """One function, one replica row.

    ``arrivals`` are float32 inter-arrival gaps, or float64 absolute
    timestamps when ``prestamped`` (entries past ``sim_time`` are inert).
    Counts and response sums cover arrivals after ``skip_time``; the
    integrals cover ``(skip_time, sim_time]``.
    """
    r = rnd or _same
    t_exp = r(float(expiration_threshold))
    arr = np.asarray(arrivals, np.float64 if prestamped else np.float32).tolist()
    ws = np.asarray(warms, np.float32).tolist()
    cs = np.asarray(colds, np.float32).tolist()
    busy: list = []  # busy_until per live instance, in creation order
    n_cold = n_warm = n_reject = 0
    run_t = idle_t = cold_s = warm_s = 0.0
    t_prev = 0.0

    def integrate(lo, hi):
        nonlocal run_t, idle_t
        if hi <= lo:
            return
        for b in busy:
            run = r(min(b, hi) - lo)
            if run > 0:
                run_t = r(run_t + run)
            idle = r(min(r(b + t_exp), hi) - max(b, lo))
            if idle > 0:
                idle_t = r(idle_t + idle)

    for a, w, c in zip(arr, ws, cs):
        t = a if prestamped else r(t_prev + a)
        integrate(min(max(t_prev, skip_time), sim_time),
                  min(max(t, skip_time), sim_time))
        if busy:
            busy = [b for b in busy if r(b + t_exp) > t]
        t_prev = t
        if t > sim_time:
            continue
        counted = t > skip_time
        pick = -1
        for j in range(len(busy) - 1, -1, -1):  # newest idle first
            if busy[j] <= t:
                pick = j
                break
        if pick >= 0:
            busy[pick] = r(t + w)
            if counted:
                n_warm += 1
                warm_s = r(warm_s + w)
        elif len(busy) < max_concurrency:
            busy.append(r(t + c))
            if counted:
                n_cold += 1
                cold_s = r(cold_s + c)
        elif counted:
            n_reject += 1
    integrate(max(t_prev, skip_time), sim_time)
    return dict(
        n_cold=n_cold,
        n_warm=n_warm,
        n_reject=n_reject,
        time_running=run_t,
        time_idle=idle_t,
        sum_cold_resp=cold_s,
        sum_warm_resp=warm_s,
    )


def pooled(rows: list, measured_time: float) -> dict:
    """Per-cell metrics over the replica rows of one cell, as a planner
    reads them: probabilities pooled over replicas, instance counts and
    goodput as replica means over the measured time."""
    s = {k: np.asarray([row[k] for row in rows], np.float64) for k in SINGLE_FIELDS}
    served = s["n_cold"] + s["n_warm"]
    n_req = served + s["n_reject"]
    running = s["time_running"].mean() / measured_time
    idle = s["time_idle"].mean() / measured_time
    total = s["time_running"] + s["time_idle"]
    return dict(
        cold_start_prob=s["n_cold"].sum() / max(served.sum(), 1),
        rejection_prob=s["n_reject"].sum() / max(n_req.sum(), 1),
        avg_server_count=running + idle,
        avg_running_count=running,
        avg_idle_count=idle,
        wasted_ratio=s["time_idle"].sum() / max(total.sum(), 1e-12),
        avg_response_time=(s["sum_cold_resp"] + s["sum_warm_resp"]).sum()
        / max(served.sum(), 1),
        goodput=served.mean() / max(measured_time, 1e-12),
    )


def relative_gap(a: float, b: float) -> float:
    """|a - b| relative to |b|; 0 where both are 0, inf where only b is."""
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / abs(b) if b != 0 else math.inf


def call(arg):
    """``fn(*job)`` at ``precision``: one reference row, as a worker
    process runs it."""
    fn, job, precision = arg
    return fn(*job, rnd=rounding(precision) if precision else None)
