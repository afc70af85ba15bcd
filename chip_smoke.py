"""Chip smoke: drive the simulator's main path once on a TPU, through the
public entry points, at the sizes its users sweep — and check the answers.

    python chip_smoke.py               # one chip: phases (a)-(e) + pyref
    python chip_smoke.py --four-chips  # four chips: the grid-sharded sweep

Phases (one process; the chip belongs to it alone):

a. device gate — the first device must be a TPU, else exit non-zero;
b. Table 1 — ``scenario.run`` on the f64 scan at the paper's scale;
c. what-if sweep — 16 thresholds x 16 rates x 16 replicas (4096 rows) at
   the f32 decision-exact horizon, on ``scan``, on ``pallas`` with staged
   draws and on ``pallas`` with fused draws;
d. fleet — the 8-function catalog fleet over 6 thresholds x 16 replicas
   on ``scan`` and ``pallas``;
e. online — ``OnlineWhatIfService`` ticks on ``scan`` (overlapped) and on
   ``pallas``;
pyref — the f64 scan against the event-driven host oracle.

Every phase runs its work twice: the first pass is set-up (compiles), the
second must add zero traces.  Every pallas result is compared with its
jnp ``ref`` mirror on the same draws under the bound the test suite holds
that pair to (bitwise).  A failed check exits non-zero; the last line of a
passing run is the JSON contract line ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _trace_snapshot() -> dict:
    from repro.core import scenario, simulator
    from repro.kernels import faas_event_step

    return {
        f"{mod.__name__}.{k}": v
        for mod in (scenario, simulator, faas_event_step)
        for k, v in mod.TRACE_COUNTS.items()
        if v
    }


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not measured"
    return str(int(stats["peak_bytes_in_use"]))


def twice(label: str, fn):
    """Run ``fn`` cold (set-up: traces and compiles) then warm; the warm
    pass must add no trace.  Returns the warm result and both times."""
    t0 = time.perf_counter()
    fn()
    setup_s = time.perf_counter() - t0
    before = _trace_snapshot()
    t0 = time.perf_counter()
    out = fn()
    wall_s = time.perf_counter() - t0
    after = _trace_snapshot()
    added = {
        k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)
    }
    check(not added, f"{label}: warm pass traced again: {added}")
    return out, wall_s, setup_s


def report(phase: str, **fields) -> None:
    """One line per phase: wall, set-up and peak device bytes, then the
    phase's own numbers."""
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {extra} peak_bytes_in_use={_peak_bytes()}", flush=True)


def _max_abs_diff(a, b, fields) -> dict:
    out = {}
    for f in fields:
        x, y = getattr(a, f), getattr(b, f)
        if x is None and y is None:
            continue
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        check(x.shape == y.shape, f"{f}: shape {x.shape} != {y.shape}")
        same = (x == y) | (np.isnan(x) & np.isnan(y))
        out[f] = 0.0 if same.all() else float(np.nanmax(np.abs(x - y)))
    return out


def bitwise(label: str, a, b) -> dict:
    """Per-cell bitwise equality of every metric grid of two results."""
    diff = _max_abs_diff(a, b, type(a)._METRIC_FIELDS)
    bad = {f: d for f, d in diff.items() if d != 0.0}
    check(not bad, f"{label}: not bitwise equal, max |diff| per metric {bad}")
    return diff


def all_ok(label: str, g) -> None:
    ok = np.asarray(g.ok)
    check(ok.all(), f"{label}: {int((~ok).sum())} of {ok.size} cells not finite")


def table1_scenario(**kw):
    """The paper's AWS Lambda function (Table 1)."""
    from repro.core import ExpSimProcess, Scenario

    d = dict(
        arrival_process=ExpSimProcess(rate=0.9),
        warm_service_process=ExpSimProcess(rate=1 / 1.991),
        cold_service_process=ExpSimProcess(rate=1 / 2.244),
        expiration_threshold=600.0,
        sim_time=2e5,
        skip_time=100.0,
        slots=64,
    )
    d.update(kw)
    return Scenario(**d)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_gate(want_count: int = 1) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    print(f"[gate] platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(d0.platform == "tpu", f"no TPU: the first device is {d0.platform!r}")
    check(len(devs) >= want_count, f"need {want_count} devices, see {len(devs)}")
    return dev


def phase_table1(sim_time: float = 2e5, replicas: int = 4) -> dict:
    """Table 1 on the f64 scan; the paper's values beside ours, held to
    the bounds of the test suite's Table 1 reproduction."""
    from repro.core import scenario

    scn = table1_scenario(sim_time=sim_time)
    res, wall, setup = twice(
        "table1", lambda: scenario.run(scn, jax.random.key(42), replicas=replicas)
    )
    s = res.summary
    vals = {
        "cold_start_prob": (s.cold_start_prob, 0.0014),
        "avg_server_count": (s.avg_server_count, 7.6795),
        "avg_running_count": (s.avg_running_count, 1.7902),
        "avg_idle_count": (s.avg_idle_count, 5.8893),
        "rejection_prob": (s.rejection_prob, 0.0),
    }
    for k, (v, want) in vals.items():
        check(np.isfinite(v), f"table1: {k} not finite")
    check(abs(s.avg_running_count - 1.7902) < 0.05, "table1: running off the paper")
    check(abs(s.avg_server_count - 7.6795) < 0.5, "table1: servers off the paper")
    check(abs(s.avg_idle_count - 5.8893) < 0.5, "table1: idle off the paper")
    check(0.0005 < s.cold_start_prob < 0.004, "table1: cold-start prob off the paper")
    check(s.rejection_prob == 0.0, "table1: rejections")
    report(
        "table1", wall_s=f"{wall:.3f}", setup_s=f"{setup:.3f}",
        sim_time=sim_time, replicas=replicas,
        **{k: f"{v:.6g}(paper {want})" for k, (v, want) in vals.items()},
    )
    return {"wall_s": wall, "setup_s": setup}


def sweep_case(n_thr, n_rate, replicas, sim_time, slots=64):
    """The what-if grid of phase (c): Table 1's function over keep-alive
    thresholds 60-1200 s × arrival rates 0.1-1.5/s.  Returns the base
    scenario, the ``sweep`` keyword arguments and the stream length."""
    from repro.core import ExpSimProcess

    scn = table1_scenario(sim_time=sim_time, skip_time=100.0, slots=slots)
    steps = table1_scenario(
        sim_time=sim_time, arrival_process=ExpSimProcess(rate=1.5)
    ).steps_needed()
    over = {
        "expiration_threshold": list(np.linspace(60.0, 1200.0, n_thr)),
        "arrival_rate": list(np.linspace(0.1, 1.5, n_rate)),
    }
    kw = dict(over=over, key=jax.random.key(7), replicas=replicas, steps=steps)
    return scn, kw, steps


def phase_sweep(
    n_thr: int = 16, n_rate: int = 16, replicas: int = 16,
    sim_time: float = 1e4, slots: int = 64,
) -> dict:
    """The what-if grid on scan, pallas staged and pallas fused; each
    pallas sweep bitwise against its ``ref`` mirror on the same draws."""
    from repro.core import Execution, scenario

    scn, kw, steps = sweep_case(n_thr, n_rate, replicas, sim_time, slots)
    rows = n_thr * n_rate * replicas
    grids = {}
    for name, plan, ref_plan in (
        ("scan", Execution(), None),
        ("pallas_staged", Execution(backend="pallas"), Execution(backend="ref")),
        (
            "pallas_fused",
            Execution(backend="pallas", draws="fused"),
            Execution(backend="ref", draws="fused"),
        ),
    ):
        g, wall, setup = twice(
            f"sweep/{name}", lambda p=plan: scenario.sweep(scn, execution=p, **kw)
        )
        all_ok(f"sweep/{name}", g)
        fields = dict(
            wall_s=f"{wall:.3f}", setup_s=f"{setup:.3f}", rows=rows, steps=steps,
            block_k=g.execution.block_k,
            cold_prob_mean=f"{float(np.mean(g.cold_start_prob)):.6g}",
        )
        if ref_plan is not None:
            ref, ref_wall, ref_setup = twice(
                f"sweep/{name}/ref",
                lambda p=ref_plan: scenario.sweep(scn, execution=p, **kw),
            )
            diff = bitwise(f"sweep/{name} vs ref", g, ref)
            fields.update(
                ref_wall_s=f"{ref_wall:.3f}", ref_setup_s=f"{ref_setup:.3f}",
                vs_ref_max_abs_diff=max(diff.values()),
            )
        if name == "pallas_staged":
            # f32 vs f64 on the same draws: no test bound holds the pair at
            # this scale (rare near-tie decisions flip); printed for the record
            scan = grids["scan"]
            fields["vs_scan_server_count_max_rel"] = float(
                np.max(np.abs(g.avg_server_count - scan.avg_server_count)
                       / np.abs(scan.avg_server_count))
            )
        report(f"sweep/{name}", **fields)
        grids[name] = g
    n_custom = _pallas_custom_calls(rows, steps, slots)
    if jax.default_backend() == "tpu":  # (interpreted on the CPU)
        check(n_custom > 0, "lowered pallas sweep has no tpu_custom_call")
    print(f"[sweep/hlo] tpu_custom_call={n_custom}", flush=True)
    return grids


def _pallas_custom_calls(rows: int, steps: int, slots: int) -> int:
    """Lower the sweep kernel at the phase's shapes, in the mode its
    launcher picks on this platform, and count the Mosaic custom calls."""
    import jax.numpy as jnp

    from repro.core.execution import Execution
    from repro.kernels import faas_event_step as fe

    R, K = rows, steps  # the kernel program pads both itself
    bk = Execution().resolved_block_k(steps)
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    text = fe.faas_sweep_pallas.lower(
        S(R, slots), S(R, slots), S(R, slots), S(R), S(R),
        S(R, K), S(R, K), S(R, K), t_end=S(R), skip=S(R),
        max_concurrency=slots, block_k=bk, interpret=fe._interpret(),
    ).as_text()
    return text.count("tpu_custom_call")


def phase_fleet(
    n_thr: int = 6, replicas: int = 16, sim_time: float = 4000.0,
) -> dict:
    """The 8-function catalog fleet under a shared 24-instance cluster on
    scan and pallas; pallas bitwise against the fleet ``ref`` mirror."""
    from repro.core import fleet_sweep
    from repro.data.catalog import catalog_names, fleet_of

    fleet = fleet_of(
        catalog_names(), n_cluster=24, sim_time=sim_time, skip_time=20.0,
        slots=64,
    )
    kw = dict(
        over={"expiration_threshold": list(np.linspace(60.0, 1200.0, n_thr))},
        key=jax.random.key(11), replicas=replicas,
    )
    grids = {}
    for backend in ("scan", "pallas"):
        g, wall, setup = twice(
            f"fleet/{backend}", lambda b=backend: fleet_sweep(fleet, backend=b, **kw)
        )
        all_ok(f"fleet/{backend}", g)
        fields = dict(
            wall_s=f"{wall:.3f}", setup_s=f"{setup:.3f}",
            functions=len(fleet.functions), cells=n_thr, replicas=replicas,
            peak_cluster=float(np.max(g.peak_cluster)),
        )
        if backend == "pallas":
            ref, ref_wall, ref_setup = twice(
                "fleet/ref", lambda: fleet_sweep(fleet, backend="ref", **kw)
            )
            diff = bitwise("fleet/pallas vs ref", g, ref)
            fields.update(
                ref_wall_s=f"{ref_wall:.3f}", ref_setup_s=f"{ref_setup:.3f}",
                vs_ref_max_abs_diff=max(diff.values()),
            )
        report(f"fleet/{backend}", **fields)
        grids[backend] = g
    return grids


def phase_online(
    n_ticks: int = 5, replicas: int = 4, n_bins: int = 16,
    bin_width: float = 60.0,
) -> dict:
    """``OnlineWhatIfService`` on the Table 1 function: overlapped ticks on
    scan, synchronous ticks on pallas.  The tick's trace delta is 1 (plus
    1 where the warmup traces the draw-staging program) then 0, and each
    recommendation replays bitwise offline."""
    from repro.core import Execution, SinusoidalRate, scenario
    from repro.serving import OnlineConfig, OnlineWhatIfService, replay_arrivals

    span = n_bins * bin_width / 4
    stream = replay_arrivals(
        SinusoidalRate(base=0.9, amplitude=0.45, period=4 * span),
        n_ticks * span, key=jax.random.key(3),
    )
    out = {}
    for backend, overlap in (("scan", True), ("pallas", False)):
        cfg = OnlineConfig(
            rate_ceiling=3.0, n_bins=n_bins, bin_width=bin_width,
            replicas=replicas, overlap=overlap,
            execution=Execution(backend=backend),
        )
        svc = OnlineWhatIfService(table1_scenario(), cfg)
        deltas, tick_s, recs = [], [], []
        stage_before = scenario.TRACE_COUNTS["stage_draw_cells"]
        for i in range(n_ticks):
            svc.observe(stream[(stream >= i * span) & (stream < (i + 1) * span)])
            before = scenario.TRACE_COUNTS["online_tick"]
            t0 = time.perf_counter()
            rec = svc.tick()
            tick_s.append(time.perf_counter() - t0)
            deltas.append(scenario.TRACE_COUNTS["online_tick"] - before)
            recs.append(rec)
            if i == 0:
                stage_delta = (
                    scenario.TRACE_COUNTS["stage_draw_cells"] - stage_before
                )
        recs.append(svc.flush())
        recs = [r for r in recs if r is not None]
        label = f"online/{backend}"
        # the warmup tick traces the sweep, and the draw staging unless an
        # earlier phase of this process traced it at the same shapes
        check(stage_delta in (0, 1)
              and deltas[0] == 1 + stage_delta and not any(deltas[1:]),
              f"{label}: online_tick trace deltas {deltas} (staging traced "
              f"{stage_delta}), want {1 + stage_delta} then 0")
        check(len(recs) == n_ticks, f"{label}: {len(recs)} recommendations")
        for rec in recs:
            check(not rec.degraded, f"{label}: tick {rec.tick} degraded: "
                  f"{rec.degraded_reason}")
            all_ok(label, rec.grid)
        diff = bitwise(f"{label} offline replay", svc.offline_equivalent(recs[-1]),
                       recs[-1].grid)
        report(
            label, wall_s=f"{sum(tick_s[1:]):.3f}", setup_s=f"{tick_s[0]:.3f}",
            ticks=n_ticks, overlap=overlap,
            tick_ms=",".join(f"{1e3 * t:.1f}" for t in tick_s),
            trace_deltas=deltas, threshold=recs[-1].applied_threshold,
            offline_max_abs_diff=max(diff.values()),
        )
        out[backend] = recs
    return out


def phase_pyref(sim_time: float = 4000.0, replicas: int = 2) -> dict:
    """The f64 scan on the device against the event-driven host oracle on
    the same draws: decision-exact counts, integrals within the suite's
    1e-9 relative bound."""
    from repro.core import ServerlessSimulator
    from repro.core.pyref import simulate_pyref

    scn = table1_scenario(sim_time=sim_time, expiration_threshold=60.0)
    sim = ServerlessSimulator(scn)
    key = jax.random.key(5)
    samples = sim.draw_samples(key, replicas)
    s, wall, setup = twice("pyref", lambda: sim.run(key, samples=samples))
    dts, warms, colds = (np.asarray(x) for x in samples)
    worst = 0.0
    for r in range(replicas):
        ref = simulate_pyref(
            dts[r], warms[r], colds[r], scn.expiration_threshold,
            scn.max_concurrency, scn.sim_time, scn.skip_time,
        )
        for f in ("n_cold", "n_warm", "n_reject"):
            check(int(getattr(s, f)[r]) == getattr(ref, f),
                  f"pyref: replica {r} {f} {int(getattr(s, f)[r])} != "
                  f"{getattr(ref, f)}")
        for f in ("time_running", "time_idle"):
            a, b = float(getattr(s, f)[r]), float(getattr(ref, f))
            rel = abs(a - b) / max(abs(b), 1e-300)
            worst = max(worst, rel)
            check(rel <= 1e-9, f"pyref: replica {r} {f} rel diff {rel:.3e}")
    report(
        "pyref", wall_s=f"{wall:.3f}", setup_s=f"{setup:.3f}", replicas=replicas,
        arrivals=int(np.sum(s.n_cold) + np.sum(s.n_warm) + np.sum(s.n_reject)),
        decisions="exact", integral_max_rel=f"{worst:.3e}",
    )
    return {"integral_max_rel": worst}


def phase_four_chips(
    n_thr: int = 16, n_rate: int = 16, replicas: int = 16,
    sim_time: float = 1e4, n_devices: int = 4,
) -> dict:
    """The phase (c) grid sharded over ``n_devices`` (``shard="grid"``) on
    scan and pallas, against the same grid on one device: per-cell bitwise.
    The mesh must span every device, not only the first."""
    from repro.core import Execution, scenario

    scn, kw, _ = sweep_case(n_thr, n_rate, replicas, sim_time)
    devs = jax.devices()[:n_devices]
    check(len({d.id for d in devs}) == n_devices,
          f"want {n_devices} distinct devices, see {len(devs)}")
    out = {}
    for backend in ("scan", "pallas"):
        single, wall1, setup1 = twice(
            f"4chip/{backend}/single",
            lambda b=backend: scenario.sweep(scn, execution=Execution(backend=b), **kw),
        )
        plan = Execution(backend=backend, devices=n_devices, shard="grid")
        mesh_ids = sorted(d.id for d in plan.mesh().devices.flat)
        check(mesh_ids == sorted(d.id for d in devs),
              f"4chip/{backend}: mesh spans devices {mesh_ids}")
        # the single-device runs used only the first device, so a peak
        # that rises on each of the others shows the shards landed there
        others = devs[1:]
        before = [_device_bytes(d) for d in others]
        shard, wall4, setup4 = twice(
            f"4chip/{backend}/sharded",
            lambda p=plan: scenario.sweep(scn, execution=p, **kw),
        )
        grew = [_grew(b, _device_bytes(d)) for b, d in zip(before, others)]
        check(all(g is not False for g in grew),
              f"4chip/{backend}: devices with no sharded work: "
              f"{[d.id for d, g in zip(others, grew) if g is False]}")
        all_ok(f"4chip/{backend}", shard)
        diff = bitwise(f"4chip/{backend} sharded vs single", shard, single)
        report(
            f"4chip/{backend}", wall_s=f"{wall4:.3f}", setup_s=f"{setup4:.3f}",
            single_wall_s=f"{wall1:.3f}", single_setup_s=f"{setup1:.3f}",
            devices=n_devices, rows=n_thr * n_rate * replicas,
            mesh_devices=mesh_ids,
            other_devices_with_work=(
                "not measured" if None in grew else len(others)
            ),
            vs_single_max_abs_diff=max(diff.values()),
        )
        out[backend] = (single, shard)
    return out


def _device_bytes(d):
    stats = d.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _grew(before, after):
    """Whether a device's peak memory rose; None where it is not reported."""
    if before is None or after is None:
        return None
    return after > before


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--four-chips", action="store_true",
        help="run only the grid-sharded sweep over 4 chips vs one chip",
    )
    args = p.parse_args(argv)
    try:
        dev = device_gate(4 if args.four_chips else 1)
        from repro.core.execution import enable_compile_cache

        print(f"[setup] compile_cache={enable_compile_cache()}", flush=True)
        if args.four_chips:
            phase_four_chips()
        else:
            phase_table1()
            phase_sweep()
            phase_fleet()
            phase_online()
            phase_pyref()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
