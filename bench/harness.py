"""One run of one benchmark cell.

The cell's entry in ``BENCHMARK.json`` names a configuration and a
traffic mix; both are data files found by name
(``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``).  The
traffic names its kind, a file of its own, ``bench/kinds/<kind>.py``,
that turns the two into requests and checks them (``bench/mixes.py``).
Each per-layer metric is a reader of its own,
``bench/metrics/<metric>.py``, with a function ``read(ctx)`` that returns
a number or ``None`` where it finds nothing to read.  All are found by
name in the checkout at ``root``.  A new cell or metric is new files and
entries, and so is a new kind; no code here changes.

A run: gate on the device, turn on the compilation cache inside the
checkout, build the traffic from the seed, warm up (the whole of
``setup_s``), then send requests back to back in a closed loop until
``seconds`` have passed, whole requests only.  After the window: the
trace counters and JAX's compile events must not have moved inside it,
the peak device memory is read, and a sample of the finished requests
is compared with the plain reference.  The last line of standard output
is the result.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import math
import multiprocessing
import os
import statistics
import sys
import tempfile
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHECK_SALT = 2  # seeds the sample of requests the reference checks


# worker processes of the reference; the chip's host keeps two cores for the
# harness and the runtime
POOL_SIZE = min(8, max(1, (os.cpu_count() or 2) - 2))


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no {what} named {name!r}; {what}s: {[i['name'] for i in items]}")


def load_cell(spec: dict, workload: str, root: str = ROOT):
    """``(cell, config, traffic)`` of a workload, read from its files."""
    cell = _named(spec["workloads"], workload, "workload")
    conf_entry = _named(spec["configs"], cell["config"], "config")
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def reports(metric: dict, cell: dict, spec: dict) -> bool:
    """Whether ``cell`` reports ``metric``: listed under its ``workloads``,
    or, without that key, every cell that reports the end-to-end metric
    it moves (an end-to-end metric without the key: every cell)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    return reports(_named(spec["end_to_end"], moves, "end-to-end metric"), cell, spec)


def reader(name: str, root: str = ROOT):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table['devices'])}"
        ) from None


def gate(chips: int) -> dict:
    """The device record; raises ``NoDevice`` unless the first device is
    a TPU and there are at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoDevice(f"no TPU: the first device is {d0.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def enable_cache() -> None:
    """JAX's persistent compilation cache at the fixed ``<checkout>/.jax_cache``,
    every program in it however fast it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileEvents:
    """JAX's trace, lowering, compile and cache-load events, counted and
    timed; ``mark`` starts a fresh count."""

    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/cache_retrieval")

    def __init__(self):
        import jax

        self.count = collections.Counter()
        self.secs = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith(self.PREFIXES):
            self.count[event] += 1
            self.secs[event] += secs

    def mark(self) -> None:
        self.count.clear()
        self.secs.clear()

    def total(self) -> int:
        return sum(self.count.values())

    def compile_s(self) -> float:
        return sum(v for k, v in self.secs.items() if "backend_compile" in k or "cache_retrieval" in k)


def trace_total() -> int:
    """Sum of every trace counter of the program (``TRACE_COUNTS`` of
    each of its modules that keeps one)."""
    total = 0
    for name, mod in list(sys.modules.items()):
        counts = getattr(mod, "TRACE_COUNTS", None) if name.startswith("repro.") else None
        if isinstance(counts, collections.Counter):
            total += sum(counts.values())
    return total


def memory_peak(n: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def reference_pool():
    """Worker processes for the plain reference (``None``: run it here).
    They are spawned, never forked, and import no JAX, so they leave the
    chip to this process."""
    if POOL_SIZE <= 1:
        return None
    return multiprocessing.get_context("spawn").Pool(POOL_SIZE)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


END_TO_END = {
    "sim_arrivals_per_s": lambda w: w["work"] / w["window_s"],
    "tick_p50_ms": lambda w: 1e3 * statistics.median(w["latency_s"]),
    "tick_p95_ms": lambda w: 1e3 * percentile(w["latency_s"], 95.0),
    "setup_s": lambda w: w["setup_s"],
}


class Context:
    """What a per-layer metric reader is given: the trace's ``events``
    (``bench/traces.py``), the ``requests`` of the traced window (each with
    its ``work`` and ``kernel_bytes``), and the device's ``peaks``."""

    def __init__(self, events, requests, peaks_):
        self.events = events
        self.requests = requests
        self.peaks = peaks_


def _window(mix, first: int, seconds: float, annotate: bool):
    """Closed loop from request ``first`` until ``seconds`` have passed;
    returns ``(done, latencies, window_s, failed)``."""
    import jax

    span = jax.profiler.TraceAnnotation if annotate else None
    done, lat, failed = [], [], 0
    i = first
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        try:
            if span is None:
                result = mix.request(i)
            else:
                with span("bench.request"):
                    result = None
                    for name, phase in mix.phases():
                        with span(name):
                            result = phase(i)
        except Exception:  # a request that raises counts as failed
            traceback.print_exc()
            result, ok = None, False
        else:
            ok = mix.ok(result)
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        if ok:
            done.append((i, result))
        else:
            failed += 1
        i += 1
        if t1 - t_start >= seconds:
            return done, lat, t1 - t_start, failed


def run(workload: str, seed: int, seconds: float, trace: bool, *, t_process: float,
        root: str = ROOT, keep_trace=None) -> dict:
    """One run of ``workload`` from the checkout at ``root``; returns the
    result record, also printed as the last line of standard output."""
    out, err = sys.stdout, sys.stderr
    t_imp = time.perf_counter()
    import jax

    spec = load_spec(root)
    cell, config, traffic = load_cell(spec, workload, root)
    device = gate(int(cell["chips"]))
    t_dev = time.perf_counter()
    enable_cache()
    events = CompileEvents()
    sys.path.insert(0, os.path.join(root, "src"))
    import mixes  # noqa: E402  (imports the program)

    mix = mixes.build(config, traffic, seed, root)
    warm = int(traffic.get("warmup_requests", 1))
    mix.prepare(warm + int(traffic.get("max_requests", 0)))
    t_build = time.perf_counter()
    for i in range(warm):
        mix.request(i)
    t_warm = time.perf_counter()
    setup_s = t_warm - t_process
    print(
        f"[setup] setup_s={setup_s!r} import_s={t_imp - t_process + t_build - t_dev!r} "
        f"device_init_s={t_dev - t_imp!r} warm_requests={warm} warm_s={t_warm - t_build!r} "
        f"of_which_compile_or_cache_load_s={events.compile_s()!r} compile_events={events.total()}",
        file=out, flush=True,
    )
    counts0 = trace_total()
    events.mark()
    log_dir = None
    if trace:
        log_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        from traces import options

        jax.profiler.start_trace(log_dir.name, profiler_options=options())
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    try:
        done, lat, window_s, failed = _window(mix, warm, seconds, annotate=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    new_traces = trace_total() - counts0
    compiles = events.total()
    peak = memory_peak(int(cell["chips"]))
    attempted = len(lat)
    work = sum(mix.work(i) for i, _ in done)
    w = dict(work=work, window_s=window_s, latency_s=lat, setup_s=setup_s)
    print(
        f"[window] requests={attempted} failed={failed} window_s={window_s!r} "
        f"work={work!r} latency_s_min={min(lat)!r} latency_s_max={max(lat)!r} "
        f"new_traces={new_traces} compile_events={compiles} memory_peak_bytes={peak}",
        file=out, flush=True,
    )
    kb = mix.kernel_bytes(warm)
    if kb:
        ops = mix.kernel_ops(warm)
        for name, nbytes in kb.items():
            print(f"[count] {name}: bytes_per_request={nbytes} ops_per_request={ops[name]} "
                  f"ops_per_byte={ops[name] / nbytes!r}", file=out, flush=True)

    result = {"correct": False, "attempted": attempted, "failed": failed}
    metrics = {}
    breakdown = None
    if trace:
        import traces

        ev = traces.load(log_dir.name, int(cell["chips"]))
        log_dir.cleanup()
        if keep_trace:
            ev.to_json(keep_trace)
        ctx = Context(ev, [dict(work=mix.work(i), kernel_bytes=mix.kernel_bytes(i))
                           for i, _ in done], peaks(device["kind"], root))
        for m in spec["per_layer"]:
            if reports(m, cell, spec):
                value = reader(m["name"], root).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=traces.busy_s(ev) or 0.0, window_s=traces.window_s(ev))
        breakdown = {"device_ops": traces.top_ops(ev), "idle_gaps": traces.idle_gaps(ev)}
    else:
        for m in spec["end_to_end"]:
            if reports(m, cell, spec):
                metrics[m["name"]] = {"value": END_TO_END[m["name"]](w), "unit": m["unit"]}
    device["memory_peak_bytes"] = peak

    # the comparison with the plain reference, once the window has closed
    import numpy as np

    t_check = time.perf_counter()
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), CHECK_SALT]))
    pool = reference_pool()
    try:
        numbers = mix.check(done, rng, pool)
    finally:
        if pool is not None:  # every worker ends before the result is printed
            pool.close()
            pool.join()
    limits = dict(traffic["check"]["limits"], window_new_traces=0, window_compile_events=0,
                  failed_requests=0)
    numbers = dict(numbers, window_new_traces=new_traces, window_compile_events=compiles,
                   failed_requests=failed)
    checks = {}
    for name, limit in limits.items():
        checks[name] = {"value": numbers[name], "limit": limit}
    correct = bool(done) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()
    )
    print(f"[check] seconds={time.perf_counter() - t_check!r} "
          + " ".join(f"{k}={v!r}" for k, v in numbers.items() if k not in checks),
          file=out, flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err, flush=True)

    result.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), file=out, flush=True)
    return result
