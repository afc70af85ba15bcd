"""Benchmark harness: one function per paper table/figure + perf benches.

Prints ``name,us_per_call,derived`` CSV.  ``us_per_call`` is the measured
wall-time per primary operation (per simulated arrival for simulator
benches); ``derived`` packs the headline numbers the paper reports so the
run log doubles as the reproduction record (consumed by EXPERIMENTS.md).

The paper's AWS-trace ground truth is not reachable from this container;
Figs 6–8 use the event-driven pure-Python reference simulator as the
observation stand-in (same parameters the paper measured on Lambda), so
the MAPE numbers are sim-vs-independent-implementation.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    ExpSimProcess,
    FailurePolicy,
    Reliability,
    RetryPolicy,
    Scenario,
    ServerlessSimulator,
)
from repro.core import NHPPArrivalProcess, SinusoidalRate  # noqa: E402
from repro.core import scenario as scn_api  # noqa: E402
from repro.core import simulator as sim_mod  # noqa: E402
from repro.core.metrics import histogram_to_distribution, mape  # noqa: E402
from repro.core.pyref import simulate_pyref  # noqa: E402
from repro.core.whatif import sweep_legacy  # noqa: E402

ROWS = []
QUICK = False

# --json schema version: one object per bench with the stable keys
# {name, us_per_call, derived} plus optional structured fields
# {wall_clock_s, traces, bitdiff} so the perf trajectory is machine-
# comparable PR-over-PR (CI uploads the file as an artifact).
BENCH_SCHEMA = "simfaas-bench-v1"


def emit(name: str, us_per_call, derived: str, **extra):
    """Record one bench row.  ``extra`` carries the structured fields of
    the ``--json`` schema: ``wall_clock_s`` (dict of label → seconds),
    ``traces`` (dict of counter → count), ``bitdiff`` (float).
    ``us_per_call=None`` records a row that was not measured."""
    ROWS.append({"name": name, "us_per_call": us_per_call,
                 "derived": derived, **extra})
    us = "not measured" if us_per_call is None else f"{us_per_call:.3f}"
    print(f"{name},{us},{derived}", flush=True)


def paper_cfg(sim_time=2e5, **kw):
    d = dict(
        arrival_process=ExpSimProcess(rate=0.9),
        warm_service_process=ExpSimProcess(rate=1 / 1.991),
        cold_service_process=ExpSimProcess(rate=1 / 2.244),
        expiration_threshold=600.0,
        sim_time=sim_time,
        skip_time=100.0,
        slots=64,
    )
    d.update(kw)
    return Scenario(**d)


def bench_table1():
    """Paper Table 1: steady-state metrics for the reference workload."""
    cfg = paper_cfg()
    sim = ServerlessSimulator(cfg)
    t0 = time.perf_counter()
    s = sim.run(jax.random.key(42), replicas=4)
    dt = time.perf_counter() - t0
    n = int(s.n_requests.sum())
    derived = (
        f"cold%={100*s.cold_start_prob:.3f}(paper 0.14)"
        f" servers={s.avg_server_count:.3f}(7.6795)"
        f" running={s.avg_running_count:.3f}(1.7902)"
        f" idle={s.avg_idle_count:.3f}(5.8893)"
        f" lifespan={s.avg_lifespan:.0f}(6307.7)"
        f" reject%={100*s.rejection_prob:.2f}(0)"
    )
    emit("table1_steady_state", dt / n * 1e6, derived)
    return s


def bench_fig3_instance_distribution():
    """Fig 3: portion of time at each instance count."""
    cfg = paper_cfg(sim_time=5e4, track_histogram=True, hist_bins=33)
    sim = ServerlessSimulator(cfg)
    t0 = time.perf_counter()
    s = sim.run(jax.random.key(0), replicas=4)
    dt = time.perf_counter() - t0
    dist = histogram_to_distribution(s.histogram)
    mode = int(np.argmax(dist))
    emit(
        "fig3_instance_count_distribution",
        dt / int(s.n_requests.sum()) * 1e6,
        f"mode={mode} p(mode)={dist[mode]:.3f} mean={np.sum(np.arange(33)*dist):.2f}",
    )


def bench_fig4_ci_convergence():
    """Fig 4: 10 independent runs, 95% CI of the instance-count estimate
    (paper: <1% deviation from the mean)."""
    cfg = paper_cfg(sim_time=5e4)
    t0 = time.perf_counter()
    counts = []
    for i in range(10):
        s = ServerlessSimulator(cfg).run(jax.random.key(i), replicas=1)
        counts.append(s.avg_server_count)
    dt = time.perf_counter() - t0
    mean = float(np.mean(counts))
    half = 1.96 * np.std(counts, ddof=1) / np.sqrt(len(counts))
    emit(
        "fig4_ci_convergence",
        dt / 10 * 1e6,
        f"mean={mean:.3f} ci95_half={half:.3f} rel={100*half/mean:.2f}%(paper <1%)",
    )


def bench_fig5_whatif_thresholds():
    """Fig 5: cold-start probability vs arrival rate × expiration threshold."""
    cfg = paper_cfg(sim_time=2e4)
    rates = [0.2, 0.5, 1.0, 2.0]
    thresholds = [60.0, 300.0, 600.0, 1200.0]
    t0 = time.perf_counter()
    res = scn_api.sweep(
        cfg,
        over={"expiration_threshold": thresholds, "arrival_rate": rates},
        key=jax.random.key(1),
        replicas=2,
    )
    dt = time.perf_counter() - t0
    mono_t = bool((np.diff(res.cold_start_prob, axis=0) <= 0.02).all())
    mono_r = bool((np.diff(res.cold_start_prob, axis=1) <= 0.02).all())
    emit(
        "fig5_whatif_threshold_sweep",
        dt / (len(rates) * len(thresholds)) * 1e6,
        f"cells={len(rates)*len(thresholds)} monotone_threshold={mono_t} "
        f"monotone_rate={mono_r} "
        f"cold%[600s,0.9rps]~{100*res.cold_start_prob[2,2]:.2f}",
    )


def _sim_vs_oracle(rates, metric):
    """Shared harness for Figs 6-8: JAX sim vs event-driven oracle."""
    sim_vals, obs_vals = [], []
    for rate in rates:
        cfg = paper_cfg(
            sim_time=3e4,
            arrival_process=ExpSimProcess(rate=rate),
        )
        sim = ServerlessSimulator(cfg)
        key = jax.random.key(int(rate * 1000))
        s = sim.run(key, replicas=2)
        # independent observation run (different seed → different draws)
        obs_samples = sim.draw_samples(jax.random.key(int(rate * 1000) + 7), 1)
        dts, warms, colds = [np.asarray(x)[0] for x in obs_samples]
        ref = simulate_pyref(
            dts, warms, colds, cfg.expiration_threshold, cfg.max_concurrency,
            cfg.sim_time, cfg.skip_time,
        )
        sim_vals.append(metric(s, None))
        obs_vals.append(metric(None, ref))
    return np.array(sim_vals), np.array(obs_vals)


def bench_fig6_cold_start_probability():
    rates = [0.1, 0.3, 0.9, 2.0]
    t0 = time.perf_counter()
    sim_v, obs_v = _sim_vs_oracle(
        rates,
        lambda s, r: s.cold_start_prob if s else r.cold_start_prob,
    )
    dt = time.perf_counter() - t0
    emit(
        "fig6_cold_start_vs_rate",
        dt / len(rates) * 1e6,
        f"mape={mape(sim_v, obs_v):.1f}%(paper 12.75) "
        + " ".join(f"{r}rps:{100*v:.2f}%" for r, v in zip(rates, sim_v)),
    )


def bench_fig7_instance_count():
    rates = [0.1, 0.3, 0.9, 2.0]

    def metric(s, r):
        if s is not None:
            return s.avg_server_count
        horizon = 3e4 - 100.0
        return (r.time_running + r.time_idle) / horizon

    t0 = time.perf_counter()
    sim_v, obs_v = _sim_vs_oracle(rates, metric)
    dt = time.perf_counter() - t0
    emit(
        "fig7_avg_instances_vs_rate",
        dt / len(rates) * 1e6,
        f"mape={mape(sim_v, obs_v):.2f}%(paper 3.43) "
        + " ".join(f"{r}rps:{v:.2f}" for r, v in zip(rates, sim_v)),
    )


def bench_fig8_wasted_capacity():
    rates = [0.1, 0.3, 0.9, 2.0]

    def metric(s, r):
        if s is not None:
            return s.avg_wasted_ratio
        return r.time_idle / max(r.time_running + r.time_idle, 1e-9)

    t0 = time.perf_counter()
    sim_v, obs_v = _sim_vs_oracle(rates, metric)
    dt = time.perf_counter() - t0
    emit(
        "fig8_wasted_capacity_vs_rate",
        dt / len(rates) * 1e6,
        f"mape={mape(sim_v, obs_v):.2f}%(paper 0.17) "
        + " ".join(f"{r}rps:{100*v:.1f}%" for r, v in zip(rates, sim_v)),
    )


def bench_fig1_concurrency_value():
    """Fig 1: the concurrency value's effect on instances needed — the
    ParServerlessSimulator (Knative/Cloud Run pattern)."""
    from repro.core import ParServerlessSimulator

    cfg = paper_cfg(
        sim_time=2e4,
        arrival_process=ExpSimProcess(rate=2.0),
        expiration_threshold=60.0,
    )
    t0 = time.perf_counter()
    counts = {}
    for c in (1, 3):
        s = ParServerlessSimulator(cfg, concurrency_value=c).run(
            jax.random.key(0), replicas=2
        )
        counts[c] = s.avg_server_count
    dt = time.perf_counter() - t0
    emit(
        "fig1_concurrency_value",
        dt / 2 * 1e6,
        f"instances[c=1]={counts[1]:.2f} instances[c=3]={counts[3]:.2f} "
        f"ratio={counts[1]/counts[3]:.2f}(paper: c=3 needs fewer)",
    )


def bench_routing_policy():
    """§2 Request Routing: newest-first vs oldest-first (beyond-paper
    quantification of the McGrath & Brenner scheduling rationale)."""
    import dataclasses as dc

    t0 = time.perf_counter()
    out = {}
    for routing in ("newest", "oldest"):
        cfg = dc.replace(paper_cfg(sim_time=5e4), routing=routing)
        out[routing] = ServerlessSimulator(cfg).run(jax.random.key(0), replicas=2)
    dt = time.perf_counter() - t0
    n, o = out["newest"], out["oldest"]
    emit(
        "routing_policy_study",
        dt / 2 * 1e6,
        f"lifespan newest={n.avg_lifespan:.0f}s oldest={o.avg_lifespan:.0f}s "
        f"({n.avg_lifespan/o.avg_lifespan:.1f}x) cold% "
        f"{100*n.cold_start_prob:.3f} vs {100*o.cold_start_prob:.3f} "
        f"servers {n.avg_server_count:.2f} vs {o.avg_server_count:.2f}",
    )


def bench_sim_throughput():
    """Beyond-paper: vectorised Monte-Carlo throughput vs the event-driven
    reference (arrivals/second of simulation engine).  Two configs: the
    paper-faithful baseline and the §Perf-tuned one (unroll=4, right-sized
    pool with overflow guard, 64 replicas)."""

    def run_cfg(cfg, replicas):
        sim = ServerlessSimulator(cfg)
        samples = sim.draw_samples(jax.random.key(0), replicas)
        sim.run(jax.random.key(0), samples=samples)  # warm compile
        t0 = time.perf_counter()
        s = sim.run(jax.random.key(0), samples=samples)
        return int(s.n_requests.sum()) / (time.perf_counter() - t0)

    base_rate = run_cfg(paper_cfg(sim_time=5e4), replicas=8)
    import dataclasses as dc

    tuned = dc.replace(paper_cfg(sim_time=5e4), scan_unroll=4, slots=32)
    tuned_rate = run_cfg(tuned, replicas=64)

    cfg = paper_cfg(sim_time=5e4)
    sim = ServerlessSimulator(cfg)
    samples = sim.draw_samples(jax.random.key(0), 1)
    dts, warms, colds = [np.asarray(x) for x in samples]
    t0 = time.perf_counter()
    ref = simulate_pyref(
        dts[0], warms[0], colds[0], cfg.expiration_threshold,
        cfg.max_concurrency, cfg.sim_time, cfg.skip_time,
    )
    dt_py = time.perf_counter() - t0
    py_rate = (ref.n_cold + ref.n_warm + ref.n_reject) / dt_py
    emit(
        "perf_sim_throughput",
        1e6 / tuned_rate,
        f"baseline={base_rate:,.0f}/s tuned={tuned_rate:,.0f}/s "
        f"python_ref={py_rate:,.0f}/s speedup_vs_ref={tuned_rate/py_rate:.1f}x",
    )


def bench_fig5_sweep():
    """The single-compile batched what-if engine vs the per-cell loop.

    Baseline = ``sweep_legacy(fresh_jit=True)``: the pre-batching engine,
    where rate/threshold were static jit args and EVERY grid cell paid a
    full XLA compile.  ``us_per_call`` is the batched engine's wall-time
    per simulated arrival over the whole grid.
    """
    if QUICK:
        rates = list(np.linspace(0.5, 1.5, 3))
        thresholds = list(np.linspace(30.0, 300.0, 3))
        sim_time, steps, replicas = 1000.0, 1800, 1
    else:
        rates = list(np.linspace(0.2, 2.0, 10))
        thresholds = list(np.linspace(60.0, 1200.0, 10))
        sim_time, steps, replicas = 2000.0, 4600, 2
    cfg = paper_cfg(sim_time=sim_time, skip_time=50.0)
    key = jax.random.key(1)
    grid_cells = len(rates) * len(thresholds)
    over = {"expiration_threshold": thresholds, "arrival_rate": rates}

    # warm the batched engine's single compile, then time execution
    scn_api.sweep(cfg, over=over, key=key, replicas=replicas, steps=steps)
    t0 = time.perf_counter()
    res = scn_api.sweep(cfg, over=over, key=key, replicas=replicas, steps=steps)
    dt_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    sweep_legacy(
        cfg, rates, thresholds, key, replicas=replicas, steps=steps, fresh_jit=True
    )
    dt_legacy = time.perf_counter() - t0

    t0 = time.perf_counter()
    sweep_legacy(cfg, rates, thresholds, key, replicas=replicas, steps=steps)
    dt_loop = time.perf_counter() - t0

    arrivals = grid_cells * replicas * steps
    emit(
        "bench_fig5_sweep",
        dt_batched / arrivals * 1e6,
        f"cells={grid_cells} batched={dt_batched:.2f}s "
        f"legacy_percell_compile={dt_legacy:.2f}s cached_loop={dt_loop:.2f}s "
        f"speedup_vs_legacy={dt_legacy/dt_batched:.1f}x "
        f"speedup_vs_loop={dt_loop/dt_batched:.1f}x "
        f"cold%[mid]={100*res.cold_start_prob[len(thresholds)//2, len(rates)//2]:.2f}",
    )


def bench_pallas_block():
    """f32 block-kernel sweep backends vs the f64 scan engine.

    ``us_per_call`` is the block-ref backend's wall-time per simulated
    arrival; derived records cross-backend metric agreement (the f32
    precision-domain check).
    """
    if QUICK:
        sim_time, steps, replicas = 1000.0, 1200, 1
    else:
        sim_time, steps, replicas = 4000.0, 4400, 2
    cfg = paper_cfg(sim_time=sim_time, skip_time=100.0)
    rates, thresholds = [0.5, 0.9], [300.0, 600.0]
    over = {"expiration_threshold": thresholds, "arrival_rate": rates}
    kw = dict(key=jax.random.key(42), replicas=replicas, steps=steps)

    scan = scn_api.sweep(cfg, over=over, **kw)
    scn_api.sweep(cfg, over=over, backend="ref", **kw)  # warm compile
    t0 = time.perf_counter()
    ref = scn_api.sweep(cfg, over=over, backend="ref", **kw)
    dt_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    pal = scn_api.sweep(cfg, over=over, backend="pallas", **kw)
    dt_pal = time.perf_counter() - t0

    rel = np.abs(ref.avg_server_count / scan.avg_server_count - 1).max()
    bit = np.abs(pal.avg_server_count - ref.avg_server_count).max()
    arrivals = len(rates) * len(thresholds) * replicas * steps
    emit(
        "bench_pallas_block",
        dt_ref / arrivals * 1e6,
        f"ref={dt_ref:.2f}s pallas={dt_pal:.2f}s "
        f"max_rel_vs_f64scan={rel:.2e}(<=1e-3) pallas_vs_ref_bitdiff={bit:.1e} "
        f"backend={'tpu' if jax.default_backend()=='tpu' else 'interpret'}",
    )


def bench_nhpp_sweep():
    """Non-stationary what-if: a diurnal rate-profile sweep (NHPP thinning
    + prestamped scan) as ONE batched device call, vs the f32 block ref.

    ``us_per_call`` is the scan engine's wall-time per simulated arrival
    over the whole grid; derived records the windowed cold-start spread and
    scan-vs-ref agreement (the acceptance tolerance is 1e-3).
    """
    if QUICK:
        sim_time, replicas, n_amp, n_per = 1000.0, 1, 3, 2
    else:
        sim_time, replicas, n_amp, n_per = 4000.0, 2, 5, 2
    day = sim_time / 2.0
    profiles = [
        SinusoidalRate(base=0.9, amplitude=a, period=day / (k + 1))
        for a in np.linspace(0.1, 0.9, n_amp)
        for k in range(n_per)
    ]
    cfg = paper_cfg(
        sim_time=sim_time,
        expiration_threshold=120.0,
        window_bounds=tuple(np.linspace(0.0, sim_time, 13)),
        skip_time=0.0,
    )
    steps = int(sim_time * 0.9 * 1.9 + 300)  # envelope-rate candidate budget
    over = {"profile": profiles}
    kw = dict(key=jax.random.key(3), replicas=replicas, steps=steps)
    scn_api.sweep(cfg, over=over, **kw)  # warm the single compile
    t0 = time.perf_counter()
    res = scn_api.sweep(cfg, over=over, **kw)
    dt_scan = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = scn_api.sweep(cfg, over=over, backend="ref", **kw)
    dt_ref = time.perf_counter() - t0
    agree = np.abs(ref.windowed_cold_prob - res.windowed_cold_prob).max()
    arrivals = int(res.windowed_arrivals.sum() * replicas)
    emit(
        "bench_nhpp_sweep",
        dt_scan / max(arrivals, 1) * 1e6,
        f"profiles={len(profiles)} scan={dt_scan:.2f}s ref={dt_ref:.2f}s "
        f"windowed_cold%_range="
        f"[{100*res.windowed_cold_prob.min():.2f},"
        f"{100*res.windowed_cold_prob.max():.2f}] "
        f"ref_vs_scan_maxdiff={agree:.1e}(<=1e-3)",
    )


def bench_scenario_grid():
    """The unified Scenario API's 3-axis product grid (threshold × rate ×
    horizon): compile count + wall-clock for ONE sweep() call vs the
    legacy per-cell loop over the same cells.

    ``us_per_call`` is the grid engine's wall-time per simulated arrival;
    derived pins the trace count (the acceptance bar: 1 compile for the
    whole product grid) and the speedup vs per-cell execution.
    """
    if QUICK:
        thresholds = [60.0, 300.0]
        rates = [0.5, 1.5]
        horizons = [500.0, 1000.0]
        steps, replicas = 1800, 1
    else:
        thresholds = list(np.linspace(60.0, 1200.0, 4))
        rates = list(np.linspace(0.2, 2.0, 5))
        horizons = [500.0, 1000.0, 2000.0]
        steps, replicas = 4600, 2
    cfg = paper_cfg(sim_time=max(horizons), skip_time=50.0)
    over = {
        "expiration_threshold": thresholds,
        "arrival_rate": rates,
        "sim_time": horizons,
    }
    key = jax.random.key(1)
    kw = dict(key=key, replicas=replicas, steps=steps)

    scn_api.sweep(cfg, over=over, **kw)  # warm the single compile
    before = sim_mod.TRACE_COUNTS["simulate_sweep"]
    t0 = time.perf_counter()
    res = scn_api.sweep(cfg, over=over, **kw)
    dt_grid = time.perf_counter() - t0
    traces = sim_mod.TRACE_COUNTS["simulate_sweep"] - before

    # per-cell baseline: one legacy sweep per horizon slice (shared jit)
    t0 = time.perf_counter()
    for h in horizons:
        sweep_legacy(
            Scenario.of(cfg, sim_time=h),
            rates,
            thresholds,
            key,
            replicas=replicas,
            steps=steps,
        )
    dt_cells = time.perf_counter() - t0

    cells = len(thresholds) * len(rates) * len(horizons)
    arrivals = cells * replicas * steps
    emit(
        "bench_scenario_grid",
        dt_grid / arrivals * 1e6,
        f"cells={cells} traces={traces}(expect 0 warm) grid={dt_grid:.2f}s "
        f"percell_loop={dt_cells:.2f}s speedup={dt_cells/dt_grid:.1f}x "
        f"cold%[0,0,0]={100*res.cold_start_prob[0, 0, 0]:.2f}",
    )


def _sharded_child(quick: bool) -> dict:
    """Body of ``bench_sharded_sweep`` over the visible devices: time the
    3-axis grid single-device vs grid-sharded; returns the row payload."""
    from repro.core import Execution

    if quick:
        thresholds = [60.0, 300.0]
        rates = [0.5, 1.5]
        horizons = [500.0, 1000.0]
        steps, replicas = 1800, 2
    else:
        thresholds = list(np.linspace(60.0, 1200.0, 4))
        rates = list(np.linspace(0.2, 2.0, 5))
        horizons = [500.0, 1000.0, 2000.0]
        steps, replicas = 4600, 4
    D = len(jax.devices())
    cfg = paper_cfg(sim_time=max(horizons), skip_time=50.0)
    over = {
        "expiration_threshold": thresholds,
        "arrival_rate": rates,
        "sim_time": horizons,
    }
    kw = dict(key=jax.random.key(1), replicas=replicas, steps=steps)
    plan = Execution(shard="grid")  # all visible (fake) devices

    scn_api.sweep(cfg, over=over, **kw)  # warm the single-device compile
    scn_api.sweep(cfg, over=over, execution=plan, **kw)  # warm the sharded one
    before = (
        sim_mod.TRACE_COUNTS["simulate_sweep"],
        sim_mod.TRACE_COUNTS["simulate_sweep_sharded"],
    )
    t0 = time.perf_counter()
    single = scn_api.sweep(cfg, over=over, **kw)
    dt_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    shard = scn_api.sweep(cfg, over=over, execution=plan, **kw)
    dt_shard = time.perf_counter() - t0
    traces = (
        sim_mod.TRACE_COUNTS["simulate_sweep"] - before[0],
        sim_mod.TRACE_COUNTS["simulate_sweep_sharded"] - before[1],
    )
    bitdiff = float(
        np.abs(shard.cold_start_prob - single.cold_start_prob).max()
    )
    cells = len(thresholds) * len(rates) * len(horizons)
    arrivals = cells * replicas * steps
    return {
        "us_per_call": dt_shard / arrivals * 1e6,
        "derived": (
            f"devices={D} cells={cells} traces={traces}(expect (0, 0) warm) "
            f"single={dt_single:.2f}s sharded={dt_shard:.2f}s "
            f"scaling={dt_single / dt_shard:.2f}x bitdiff={bitdiff:.1e}(=0)"
        ),
    }


def _sharded_row(name: str, child_flag: str, body) -> None:
    """Run a 4-device sharded bench and emit its row.

    On the CPU the four devices are fake ones, and JAX fixes the device
    count at first init, so ``body`` runs in a child process started with
    ``--xla_force_host_platform_device_count=4``.  On an accelerator it
    runs here over the real devices: a child could not take the chip this
    process holds.  Fewer than 4 real devices: the row is not measured.
    """
    if jax.default_backend() != "cpu":
        n = len(jax.devices())
        if n < 4:
            emit(name, None, f"not measured: {n} device(s) visible, 4 needed")
            return
        payload = body(QUICK)
    else:
        payload = _child_payload(child_flag)
    emit(
        name,
        payload["us_per_call"],
        payload["derived"],
        **{k: payload[k] for k in ("wall_clock_s", "traces", "bitdiff")
           if k in payload},
    )


def _child_payload(child_flag: str) -> dict:
    """Run this script with ``child_flag`` on 4 fake CPU devices and
    return the JSON payload of its last output line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    args = [sys.executable, os.path.abspath(__file__), child_flag]
    if QUICK:
        args.append("--quick")
    out = subprocess.run(
        args, capture_output=True, text=True, env=env, timeout=1200
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"{child_flag} child failed (rc={out.returncode}):\n"
            f"{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_sharded_sweep():
    """Grid-sharded sweep (Execution(shard='grid')) over 4 devices: fake
    CPU devices in a child process, or the real ones (see
    :func:`_sharded_row`).  Derived reports the compile counts (expect
    zero warm traces), the single-vs-sharded wall clock and the bitwise
    check.  Fake CPU devices share the same cores — there the scaling
    number is about dispatch overhead, not real parallel speedup.
    """
    _sharded_row("bench_sharded_sweep", "--sharded-child", _sharded_child)


def _block_sharded_child(quick: bool) -> dict:
    """Body of ``bench_block_sharded``: a threshold × profile grid with
    (irregular) metric windows on the f32 block backend, single-device vs
    grid-sharded over the visible devices; returns the row payload.
    Backend: pallas on TPU, its jnp ref mirror elsewhere (interpret-mode
    pallas timing would measure the interpreter)."""
    from repro.core import Execution, scenario as scn
    from repro.core.scenario import TRACE_COUNTS as SCN_TRACE_COUNTS

    backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if quick:
        sim_time, replicas, n_thr, n_amp = 1000.0, 2, 3, 4
    else:
        sim_time, replicas, n_thr, n_amp = 4000.0, 4, 4, 8
    day = sim_time / 2.0
    profiles = [
        SinusoidalRate(base=0.9, amplitude=a, period=day)
        for a in np.linspace(0.1, 0.9, n_amp)
    ]
    bounds = np.concatenate(
        [np.linspace(0.0, sim_time / 2, 5), [sim_time * 0.8, sim_time]]
    )
    cfg = paper_cfg(
        sim_time=sim_time,
        expiration_threshold=120.0,
        window_bounds=tuple(bounds),  # irregular: in-kernel windowed path
        skip_time=0.0,
    )
    steps = int(sim_time * 0.9 * 1.9 + 300)
    over = {
        "expiration_threshold": list(np.linspace(60.0, 600.0, n_thr)),
        "profile": profiles,
    }
    kw = dict(key=jax.random.key(3), replicas=replicas, steps=steps,
              backend=backend)
    plan = Execution(backend=backend, shard="grid")  # all visible devices
    D = len(jax.devices())

    scn.sweep(cfg, over=over, **kw)  # warm the single-device compile
    scn.sweep(cfg, over=over, execution=plan, **kw)  # warm the sharded one
    before = (
        SCN_TRACE_COUNTS["sweep_block_ref"],
        SCN_TRACE_COUNTS["sweep_block_sharded"],
    )
    t0 = time.perf_counter()
    single = scn.sweep(cfg, over=over, **kw)
    dt_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    shard = scn.sweep(cfg, over=over, execution=plan, **kw)
    dt_shard = time.perf_counter() - t0
    traces = {
        "sweep_block_ref": SCN_TRACE_COUNTS["sweep_block_ref"] - before[0],
        "sweep_block_sharded": (
            SCN_TRACE_COUNTS["sweep_block_sharded"] - before[1]
        ),
    }
    bitdiff = max(
        float(np.abs(np.asarray(getattr(shard, f))
                     - np.asarray(getattr(single, f))).max())
        for f in ("cold_start_prob", "windowed_instance_count")
    )
    cells = n_thr * n_amp
    arrivals = int(single.windowed_arrivals.sum() * replicas)
    return {
        "us_per_call": dt_shard / max(arrivals, 1) * 1e6,
        "derived": (
            f"backend={backend} devices={D} cells={cells} "
            f"block_k={single.execution.block_k} "
            f"traces={tuple(traces.values())}(expect (0, 0) warm) "
            f"single={dt_single:.2f}s sharded={dt_shard:.2f}s "
            f"scaling={dt_single / dt_shard:.2f}x "
            f"bitdiff={bitdiff:.1e}(=0)"
        ),
        "wall_clock_s": {"single": dt_single, "sharded": dt_shard},
        "traces": traces,
        "bitdiff": bitdiff,
    }


def bench_block_sharded():
    """Grid-sharded f32 block sweep (the headline of the block-backend
    promotion): a threshold × profile grid with irregular metric windows
    under ``Execution(backend=<block>, shard='grid')`` over 4 devices (see
    :func:`_sharded_row`) vs single-device — expect zero warm traces and
    bitdiff=0.  Fake CPU devices share cores, so off-TPU scaling measures
    dispatch overhead.
    """
    _sharded_row("bench_block_sharded", "--block-sharded-child",
                 _block_sharded_child)


def bench_retry_sweep():
    """Reliability what-if (DESIGN.md §11): a (t_timeout × threshold) retry
    sweep as ONE batched call on the f64 scan engine, vs the f32 block ref.

    ``us_per_call`` is the scan engine's wall-time per simulated *attempt*
    over the whole grid; derived pins the trace counts (the acceptance bar:
    zero warm compiles on both backends — timeout/failure rates are traced
    param axes, ``max_retries`` stays static) plus goodput/amplification
    and the cross-backend agreement.
    """
    if QUICK:
        timeouts = [4.0, 16.0]
        thresholds = [60.0, 300.0]
        sim_time, steps, replicas = 1000.0, 1400, 1
    else:
        timeouts = [2.0, 4.0, 8.0, 16.0]
        thresholds = [30.0, 120.0, 480.0]
        sim_time, steps, replicas = 4000.0, 5400, 2
    rel = Reliability(
        failure=FailurePolicy(p_fail=0.05, t_timeout=8.0),
        retry=RetryPolicy(max_retries=2, backoff_base=2.0, backoff_jitter=0.3),
    )
    cfg = paper_cfg(
        sim_time=sim_time, skip_time=50.0, expiration_threshold=120.0,
        reliability=rel,
    )
    over = {"t_timeout": timeouts, "expiration_threshold": thresholds}
    kw = dict(key=jax.random.key(7), replicas=replicas, steps=steps)

    scn_api.sweep(cfg, over=over, **kw)  # warm the scan compile
    scn_api.sweep(cfg, over=over, backend="ref", **kw)  # warm the block ref
    before = (
        sim_mod.TRACE_COUNTS["simulate_sweep"],
        scn_api.TRACE_COUNTS["sweep_block_ref"],
    )
    t0 = time.perf_counter()
    res = scn_api.sweep(cfg, over=over, **kw)
    dt_scan = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = scn_api.sweep(cfg, over=over, backend="ref", **kw)
    dt_ref = time.perf_counter() - t0
    traces = (
        sim_mod.TRACE_COUNTS["simulate_sweep"] - before[0],
        scn_api.TRACE_COUNTS["sweep_block_ref"] - before[1],
    )

    agree = float(np.abs(ref.goodput / np.maximum(res.goodput, 1e-12) - 1).max())
    attempts = float(
        np.array([[s.n_attempts.sum() for s in row] for row in res.summaries]).sum()
    )
    amp = float(
        np.array(
            [[s.retry_amplification for s in row] for row in res.summaries]
        ).max()
    )
    cells = len(timeouts) * len(thresholds)
    emit(
        "bench_retry_sweep",
        dt_scan / max(attempts, 1.0) * 1e6,
        f"cells={cells} traces={traces}(expect (0, 0) warm) "
        f"scan={dt_scan:.2f}s block_ref={dt_ref:.2f}s "
        f"goodput[{timeouts[-1]:.0f}s,{thresholds[-1]:.0f}s]="
        f"{res.goodput[-1, -1]:.3f}/s max_retry_amp={amp:.3f}x "
        f"ref_vs_scan_goodput_rel={agree:.1e}(<=1e-3)",
        traces={"simulate_sweep": traces[0], "sweep_block_ref": traces[1]},
        wall_clock_s={"scan": dt_scan, "block_ref": dt_ref},
    )


def bench_fused_rng():
    """DrawPlan fused in-kernel RNG vs the host-staged draw stacks
    (DESIGN.md §12): the same (threshold × rate) grid with
    ``Execution(draws='fused')`` vs the staged default.

    ``us_per_call`` is the fused engine's wall-time per simulated arrival.
    Derived pins the two acceptance bars: the fused executable's HLO must
    carry NO ``[C, K]`` sample operands (the staged path stages three — the
    whole point of the refactor), and the analytic peak-HBM per grid row
    must buy a ≥2× larger max feasible grid at fixed memory.
    """
    from repro.core import Execution

    if QUICK:
        thresholds = list(np.linspace(60.0, 600.0, 4))
        rates = list(np.linspace(0.3, 1.5, 4))
        sim_time, replicas = 1000.0, 4
    else:
        thresholds = list(np.linspace(60.0, 1200.0, 8))
        rates = list(np.linspace(0.2, 2.0, 8))
        sim_time, replicas = 2000.0, 8
    steps = int(sim_time * max(rates) * 1.25) + 200  # arrival-stream budget
    cfg = paper_cfg(sim_time=sim_time, skip_time=50.0)
    over = {"expiration_threshold": thresholds, "arrival_rate": rates}
    kw = dict(key=jax.random.key(5), replicas=replicas, steps=steps)
    fused_plan = Execution(draws="fused")
    C = len(thresholds) * len(rates) * replicas
    K = steps

    # spy on the fused scan engine: capture its call args so the compiled
    # HLO can be AOT-lowered and searched for [C, K] operands afterwards
    captured = {}
    orig = sim_mod._simulate_sweep_fused

    def spy(*a):
        captured["args"] = a
        return orig(*a)

    sim_mod._simulate_sweep_fused = spy
    try:
        scn_api.sweep(cfg, over=over, execution=fused_plan, **kw)  # warm
        before = sim_mod.TRACE_COUNTS["simulate_sweep_fused"]
        t0 = time.perf_counter()
        fus = scn_api.sweep(cfg, over=over, execution=fused_plan, **kw)
        dt_fused = time.perf_counter() - t0
        traces = sim_mod.TRACE_COUNTS["simulate_sweep_fused"] - before
    finally:
        sim_mod._simulate_sweep_fused = orig

    scn_api.sweep(cfg, over=over, **kw)  # warm the staged compile
    t0 = time.perf_counter()
    stg = scn_api.sweep(cfg, over=over, **kw)
    dt_staged = time.perf_counter() - t0

    hlo = orig.lower(*captured["args"]).as_text()
    fused_has_ck = any(f"{d}[{C},{K}]" in hlo for d in ("f32", "f64", "u32"))

    # analytic peak-HBM per grid row: staged stages 3 f64[K] sample stacks
    # per row; fused carries 3 uint32[2] key rows + 3 f64[2] param rows.
    # Row state (instance pool) is common to both.
    state_row = cfg.slots * 3 * 8 + 256
    staged_row = 3 * K * 8 + state_row
    fused_row = 3 * (8 + 16) + state_row
    headroom = staged_row / fused_row
    agree = float(
        np.abs(fus.avg_server_count - stg.avg_server_count).max()
    )  # independent streams: same physics, different draws
    arrivals = C * K
    emit(
        "bench_fused_rng",
        dt_fused / arrivals * 1e6,
        f"rows={C} steps={K} staged={dt_staged:.2f}s fused={dt_fused:.2f}s "
        f"traces={traces}(expect 0 warm) "
        f"fused_hlo_has_CK={fused_has_ck}(expect False) "
        f"staged_hbm/row={staged_row/1e3:.0f}KB fused_hbm/row={fused_row/1e3:.1f}KB "
        f"grid_headroom={headroom:.0f}x(>=2) "
        f"server_count_absdiff={agree:.2f}(MC noise)",
        wall_clock_s={"staged": dt_staged, "fused": dt_fused},
        traces={"simulate_sweep_fused": traces},
        hbm_bytes_per_row={"staged": staged_row, "fused": fused_row},
        fused_hlo_has_ck=fused_has_ck,
        grid_headroom=headroom,
    )


def bench_fleet_sweep():
    """Fleet subsystem (DESIGN.md §13): an 8-function SeBS-flavored
    catalog mix under binding shared capacity, swept over a keep-alive
    threshold grid — one compile on each backend.

    ``us_per_call`` is the f64 scan's warm wall-time per simulated
    arrival.  Derived pins the acceptance bars: traces=(0,0) on the warm
    pass for both scan and block sweeps, and bitdiff=0 between the
    pallas fleet kernel and its jnp ref mirror across the whole grid.
    """
    from repro.core.fleet import fleet_sweep
    from repro.data.catalog import catalog_names, fleet_of
    from repro.kernels import faas_event_step as fe_mod

    names = list(catalog_names())  # all 8 profiles
    if QUICK:
        thresholds = [30.0, 120.0, 600.0]
        sim_time, replicas = 600.0, 2
    else:
        thresholds = list(np.linspace(60.0, 1200.0, 6))
        sim_time, replicas = 4000.0, 4
    fleet = fleet_of(
        names, n_cluster=24, sim_time=sim_time, skip_time=20.0, slots=64
    )
    over = {"expiration_threshold": thresholds}
    kw = dict(key=jax.random.key(7), replicas=replicas)

    fleet_sweep(fleet, over=over, **kw)  # warm the scan compile
    scan_before = scn_api.TRACE_COUNTS.get("fleet_sweep_scan", 0)
    t0 = time.perf_counter()
    scan = fleet_sweep(fleet, over=over, **kw)
    dt_scan = time.perf_counter() - t0
    scan_traces = scn_api.TRACE_COUNTS.get("fleet_sweep_scan", 0) - scan_before

    fleet_sweep(fleet, over=over, backend="pallas", **kw)  # warm blocks
    pal_before = fe_mod.TRACE_COUNTS.get("fleet_sweep_pallas", 0)
    t0 = time.perf_counter()
    pal = fleet_sweep(fleet, over=over, backend="pallas", **kw)
    dt_block = time.perf_counter() - t0
    pal_traces = (
        fe_mod.TRACE_COUNTS.get("fleet_sweep_pallas", 0) - pal_before
    )
    ref = fleet_sweep(fleet, over=over, backend="ref", **kw)

    bitdiff = max(
        float(
            np.abs(
                np.asarray(getattr(pal, f), np.float64)
                - np.asarray(getattr(ref, f), np.float64)
            ).max()
        )
        for f in ("cold_start_prob", "avg_response_time", "peak_cluster")
    )
    scandiff = float(
        np.abs(scan.cold_start_prob - pal.cold_start_prob).max()
    )
    arrivals = float(
        sum(
            f.arrival_process.rate * (sim_time - fleet.skip_time)
            for f in fleet.functions
        )
        * len(thresholds)
        * replicas
    )
    peak = float(np.asarray(scan.peak_cluster).max())
    emit(
        "bench_fleet_sweep",
        dt_scan / arrivals * 1e6,
        f"functions={len(names)} grid={len(thresholds)}x{len(names)} "
        f"n_cluster={fleet.n_cluster:.0f} peak={peak:.0f} "
        f"scan={dt_scan:.2f}s block={dt_block:.2f}s "
        f"traces=({scan_traces},{pal_traces})(expect (0,0) warm) "
        f"bitdiff={bitdiff}(expect 0) scan_vs_block_cold={scandiff:.4f}",
        wall_clock_s={"scan": dt_scan, "block": dt_block},
        traces={
            "fleet_sweep_scan": scan_traces,
            "fleet_sweep_pallas": pal_traces,
        },
        bitdiff=bitdiff,
    )


def bench_fault_sweep():
    """Platform fault injection (DESIGN.md §15): a crash-rate x
    keep-alive-threshold grid with capacity churn on, ONE compile per
    backend (crash rate and capacity edges/values are traced axes).

    ``us_per_call`` is the f64 scan's warm wall-time per simulated
    arrival over the whole grid.  Derived pins the acceptance bars:
    traces=(0,0) on the warm pass (scan + pallas) and bitdiff=0 between
    the pallas kernel and its jnp ref mirror across every cell — the
    fault columns ride the same accumulator, so agreement here covers
    crashes, evictions, and interrupted work too.
    """
    from repro.core.faults import CapacityProfile, FaultModel
    from repro.kernels import faas_event_step as fe_mod

    if QUICK:
        rates = [1e-3, 1e-2]
        thresholds = [60.0, 300.0]
        sim_time, steps, replicas = 1000.0, 1400, 1
    else:
        rates = [1e-4, 1e-3, 5e-3, 2e-2]
        thresholds = [30.0, 120.0, 600.0]
        sim_time, steps, replicas = 4000.0, 5400, 2
    flt = FaultModel(
        crash_rate=rates[0],
        capacity=CapacityProfile(
            edges=(sim_time * 0.4, sim_time * 0.7),
            values=(40.0, 2.0, 40.0),
        ),
    )
    cfg = paper_cfg(
        sim_time=sim_time, skip_time=50.0, expiration_threshold=120.0,
        max_concurrency=30, faults=flt,
    )
    over = {"crash_rate": rates, "expiration_threshold": thresholds}
    kw = dict(key=jax.random.key(15), replicas=replicas, steps=steps)

    scn_api.sweep(cfg, over=over, **kw)  # warm the scan compile
    scn_api.sweep(cfg, over=over, backend="pallas", **kw)  # warm the kernel
    before = (
        sim_mod.TRACE_COUNTS["simulate_sweep"],
        fe_mod.TRACE_COUNTS["faas_sweep_pallas"],
    )
    t0 = time.perf_counter()
    scan = scn_api.sweep(cfg, over=over, **kw)
    dt_scan = time.perf_counter() - t0
    t0 = time.perf_counter()
    pal = scn_api.sweep(cfg, over=over, backend="pallas", **kw)
    dt_block = time.perf_counter() - t0
    traces = (
        sim_mod.TRACE_COUNTS["simulate_sweep"] - before[0],
        fe_mod.TRACE_COUNTS["faas_sweep_pallas"] - before[1],
    )
    ref = scn_api.sweep(cfg, over=over, backend="ref", **kw)

    bitdiff = max(
        float(
            np.abs(
                np.asarray(getattr(pal, f), np.float64)
                - np.asarray(getattr(ref, f), np.float64)
            ).max()
        )
        for f in ("cold_start_prob", "avg_response_time", "availability")
    )
    crashes = float(
        np.array(
            [[s.n_crash.sum() for s in row] for row in scan.summaries]
        ).sum()
    )
    evictions = float(
        np.array(
            [[s.n_evict.sum() for s in row] for row in scan.summaries]
        ).sum()
    )
    worst = float(np.asarray(scan.availability).min())
    arrivals = float(
        cfg.arrival_process.rate
        * (sim_time - 50.0)
        * len(rates)
        * len(thresholds)
        * replicas
    )
    emit(
        "bench_fault_sweep",
        dt_scan / arrivals * 1e6,
        f"cells={len(rates)}x{len(thresholds)} "
        f"traces={traces}(expect (0, 0) warm) "
        f"scan={dt_scan:.2f}s block={dt_block:.2f}s "
        f"crashes={crashes:.0f} evictions={evictions:.0f} "
        f"worst_availability={worst:.4f} bitdiff={bitdiff}(expect 0)",
        traces={"simulate_sweep": traces[0], "faas_sweep_pallas": traces[1]},
        wall_clock_s={"scan": dt_scan, "block": dt_block},
        bitdiff=bitdiff,
    )


def bench_online_service():
    """Online what-if service (DESIGN.md §14): the live re-fit→re-sweep
    control loop.

    ``us_per_call`` is the steady-state wall time per tick (dispatch +
    drain of the previous tick, i.e. the overlapped cadence the service
    sustains).  Derived pins the acceptance bars: traces=(warmup,steady)
    must be (>=1, 0) — zero recompiles per tick after warmup — and
    bitdiff=0 between a tick's grid and an offline ``sweep()`` on the
    same fitted profile and key.
    """
    from repro.serving import (
        OnlineConfig,
        OnlineWhatIfService,
        replay_arrivals,
    )

    if QUICK:
        n_ticks, batch_span, replicas = 6, 60.0, 2
        cfg = OnlineConfig(
            rate_ceiling=4.0, n_bins=8, bin_width=30.0,
            thresholds=(30.0, 120.0, 600.0), replicas=replicas,
        )
    else:
        n_ticks, batch_span, replicas = 12, 120.0, 4
        cfg = OnlineConfig(
            rate_ceiling=4.0, n_bins=16, bin_width=60.0,
            thresholds=(30.0, 60.0, 120.0, 300.0, 600.0, 1200.0),
            replicas=replicas,
        )
    base = Scenario(
        arrival_process=ExpSimProcess(rate=0.9),
        warm_service_process=ExpSimProcess(rate=1 / 1.991),
        cold_service_process=ExpSimProcess(rate=1 / 2.244),
        slots=64,
    )
    svc = OnlineWhatIfService(base, cfg)
    truth = SinusoidalRate(base=1.2, amplitude=0.6, period=800.0)
    stream = replay_arrivals(truth, n_ticks * batch_span, key=jax.random.key(3))

    tick_s, deltas = [], []
    t_edge = 0.0
    for i in range(n_ticks):
        batch = stream[(stream >= t_edge) & (stream < t_edge + batch_span)]
        t_edge += batch_span
        svc.observe(batch)
        before = scn_api.TRACE_COUNTS["online_tick"]
        t0 = time.perf_counter()
        svc.tick()
        tick_s.append(time.perf_counter() - t0)
        deltas.append(scn_api.TRACE_COUNTS["online_tick"] - before)
    last = svc.flush()
    warm_traces, steady_traces = deltas[0], max(deltas[1:])
    steady = float(np.mean(tick_s[1:]))

    off = svc.offline_equivalent(last)
    bitdiff = max(
        float(
            np.abs(
                np.asarray(getattr(off, f), np.float64)
                - np.asarray(getattr(last.grid, f), np.float64)
            ).max()
        )
        for f in ("cold_start_prob", "developer_cost", "goodput")
    )
    emit(
        "bench_online_service",
        steady * 1e6,
        f"ticks={n_ticks} grid={len(cfg.thresholds)}x{replicas}rep "
        f"steady_tick={steady*1e3:.1f}ms warmup={tick_s[0]*1e3:.1f}ms "
        f"traces=({warm_traces},{steady_traces})(expect (>=1,0)) "
        f"offline_bitdiff={bitdiff}(expect 0) "
        f"thr={last.applied_threshold:.0f}s",
        wall_clock_s={"warmup_tick": tick_s[0], "steady_tick": steady},
        traces={
            "online_tick_warmup": warm_traces,
            "online_tick_steady": steady_traces,
        },
        bitdiff=bitdiff,
    )


def bench_kernel_event_step():
    """FaaS event-step kernel (jnp ref vs Pallas-interpret parity timing is
    covered in tests; here: throughput of the jit'd kernel ref)."""
    import jax.numpy as jnp

    from repro.kernels.ref import faas_block_step_ref

    R, M, K = 256, 64, 512
    ks = jax.random.split(jax.random.key(0), 3)
    dts = (jax.random.exponential(ks[0], (R, K)) / 0.9).astype(jnp.float32)
    warms = (jax.random.exponential(ks[1], (R, K)) * 2).astype(jnp.float32)
    colds = (jax.random.exponential(ks[2], (R, K)) * 2.2).astype(jnp.float32)
    state = (
        jnp.zeros((R, M), jnp.float32),
        jnp.full((R, M), -1e30, jnp.float32),
        jnp.full((R, M), -1e30, jnp.float32),
        jnp.zeros((R,), jnp.float32),
    )
    fn = jax.jit(
        lambda *a: faas_block_step_ref(*a, t_exp=600.0, max_concurrency=1000)
    )
    out = fn(*state, dts, warms, colds)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        out = fn(*state, dts, warms, colds)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 3
    events = R * K
    emit(
        "perf_faas_event_kernel",
        dt / events * 1e6,
        f"events_per_s={events/dt:,.0f} replicas={R} pool={M}",
    )


def main(argv=None) -> None:
    global QUICK
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--quick",
        action="store_true",
        help="reduced grids/horizons: CI smoke mode",
    )
    p.add_argument(
        "--json",
        metavar="PATH",
        default=None,
        help="also write rows as JSON (e.g. BENCH_sweep.json) for cross-PR tracking",
    )
    p.add_argument(
        "--sharded-child",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: bench_sharded_sweep's subprocess
    )
    p.add_argument(
        "--block-sharded-child",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: bench_block_sharded's subprocess
    )
    args = p.parse_args(argv)
    QUICK = args.quick
    if args.sharded_child:
        print(json.dumps(_sharded_child(QUICK)))
        return 0
    if args.block_sharded_child:
        print(json.dumps(_block_sharded_child(QUICK)))
        return 0

    from repro.core.execution import enable_compile_cache

    enable_compile_cache()
    benches = [
        bench_table1,
        bench_fig5_sweep,
        bench_scenario_grid,
        bench_sharded_sweep,
        bench_block_sharded,
        bench_pallas_block,
        bench_nhpp_sweep,
        bench_retry_sweep,
        bench_fused_rng,
        bench_fleet_sweep,
        bench_fault_sweep,
        bench_online_service,
    ]
    if not QUICK:
        benches[1:1] = [
            bench_fig3_instance_distribution,
            bench_fig4_ci_convergence,
            bench_fig5_whatif_thresholds,
        ]
        benches += [
            bench_fig1_concurrency_value,
            bench_routing_policy,
            bench_fig6_cold_start_probability,
            bench_fig7_instance_count,
            bench_fig8_wasted_capacity,
            bench_sim_throughput,
            bench_kernel_event_step,
        ]
    print("name,us_per_call,derived")
    failed = []
    for bench in benches:
        try:
            bench()
        except Exception:  # noqa: BLE001 - report every bench, then fail
            traceback.print_exc()
            failed.append(bench.__name__)

    if args.json:
        payload = {"schema": BENCH_SCHEMA, "quick": QUICK, "benchmarks": ROWS}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED benches: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
