"""The readers of the program's own spans (``repro.*``): per-request sums,
self time, names that carry metadata and the error where a span has gone
missing, on hand-made events; then the spans themselves, in a real
``jax.profiler`` trace of a tiny block sweep and three overlapped online
ticks, against the same requests run untraced."""

import dataclasses
import glob
import math
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts bench/ on the path)
import harness
import traces

SWEEP_READERS = ("host_staging_ms.sweep", "coverage_guard_ms.sweep", "assembly_ms.sweep")
TICK_READERS = ("host_staging_ms.tick", "dispatch_ms.tick", "fetch_wait_ms.tick",
                "assembly_ms.tick")

# Two requests on the harness's thread, in ns.  Request A: staging 100
# (its name carries metadata), guard 50, launch 20, a drain of 600 holding
# a fetch of 200 and an assembly of 180.  Request B: staging 40, guard 10,
# launch 30, a drain of 300 (fetch 50, assembly 100), then an assembly of
# 20 outside the drain.
SPANS = [
    (0, "bench.request", 0, 1000),
    (1, "repro.sweep.stage#cells=6#", 10, 110),
    (1, "repro.sweep.guard", 120, 170),
    (1, "repro.sweep.launch", 180, 200),
    (1, "repro.online.drain#tick=3#", 300, 900),
    (2, "repro.sweep.fetch", 310, 510),
    (2, "repro.sweep.assemble", 520, 700),
    (0, "bench.request", 1000, 2000),
    (1, "repro.sweep.stage", 1010, 1050),
    (1, "repro.sweep.guard", 1060, 1070),
    (1, "repro.sweep.launch", 1080, 1110),
    (1, "repro.online.drain#tick=4#", 1200, 1500),
    (2, "repro.sweep.fetch", 1210, 1260),
    (2, "repro.sweep.assemble", 1300, 1400),
    (1, "repro.sweep.assemble", 1600, 1620),
]

# reader -> (the span it reads, its value in ns per request)
EXPECTED = {
    "host_staging_ms.sweep": ("repro.sweep.stage", (100 + 40) / 2),
    "coverage_guard_ms.sweep": ("repro.sweep.guard", (50 + 10) / 2),
    "assembly_ms.sweep": ("repro.sweep.assemble", (180 + 100 + 20) / 2),
    "host_staging_ms.tick": ("repro.sweep.stage", (100 + 40) / 2),
    "dispatch_ms.tick": ("repro.sweep.launch", (20 + 30) / 2),
    "fetch_wait_ms.tick": ("repro.sweep.fetch", (200 + 50) / 2),
    # the drain's self time: each drain less the fetch inside it
    "assembly_ms.tick": ("repro.online.drain", ((600 - 200) + (300 - 50)) / 2),
}


def _events(spans, window=(0.0, 2000.0)):
    return traces.Events(ops=[], modules=[], spans=list(spans), window=window, n_devices=1)


def _read(name, spans):
    return harness.reader(name).read(harness.Context(_events(spans), [], {}))


@pytest.mark.parametrize("name", SWEEP_READERS + TICK_READERS)
def test_span_reader_on_hand_made_events(name):
    span, ns = EXPECTED[name]
    assert _read(name, SPANS) == pytest.approx(1e-6 * ns, rel=1e-12)
    # the path ran and the program writes spans, but not this one: an error
    rest = [s for s in SPANS if s[1].split("#")[0] != span]
    with pytest.raises(RuntimeError, match="renamed"):
        _read(name, rest)
    # a program without spans, or a window without requests: nothing to read
    assert _read(name, [s for s in SPANS if s[1].startswith("bench.")]) is None
    assert _read(name, [s for s in SPANS if not s[1].startswith("bench.")]) is None


# ---- the spans in a real profiler trace


def _same(a, b):
    """``a`` and ``b`` equal bit for bit, through dataclasses, dicts,
    tuples, object arrays and arrays (JAX keys by their key data)."""
    import jax

    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        assert type(a) is type(b)
        for f in dataclasses.fields(a):
            _same(getattr(a, f.name), getattr(b, f.name))
    elif isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _same(a[k], b[k])
    elif isinstance(a, (tuple, list)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    elif isinstance(a, np.ndarray) and a.dtype == object:
        assert a.shape == b.shape
        for x, y in zip(a.ravel(), b.ravel()):
            _same(x, y)
    elif isinstance(a, jax.Array) and jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
        np.testing.assert_array_equal(jax.random.key_data(a), jax.random.key_data(b))
    elif isinstance(a, (np.ndarray, jax.Array, np.generic, float, int)) and not isinstance(a, bool):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    else:
        assert a == b


def _sweep_requests():
    import jax

    from repro.core import Execution, ExpSimProcess, Scenario, scenario

    scn = Scenario(arrival_process=ExpSimProcess(rate=1.0),
                   warm_service_process=ExpSimProcess(rate=1.0),
                   cold_service_process=ExpSimProcess(rate=0.5),
                   slots=16, sim_time=200.0, skip_time=20.0)

    def request(i):
        return scenario.sweep(scn, over={"expiration_threshold": [30.0, 300.0],
                                         "arrival_rate": [0.5, 1.0]},
                              key=jax.random.key(i), replicas=2, steps=600,
                              execution=Execution(backend="ref"))

    return [lambda i=i: request(i) for i in range(2)], None


def _tick_requests():
    from repro.core import ExpSimProcess, Scenario
    from repro.serving import OnlineConfig, OnlineWhatIfService

    svc = OnlineWhatIfService(
        Scenario(arrival_process=ExpSimProcess(rate=1.0),
                 warm_service_process=ExpSimProcess(rate=1.0),
                 cold_service_process=ExpSimProcess(rate=0.5), slots=16),
        OnlineConfig(rate_ceiling=3.0, n_bins=4, bin_width=25.0,
                     thresholds=(30.0, 120.0, 600.0), replicas=2, overlap=True),
    )
    rng = np.random.default_rng(5)

    def request(i):
        svc.observe(np.sort(30.0 * i + rng.uniform(0.0, 30.0, 25)))
        return svc.tick()

    return [lambda i=i: request(i) for i in range(3)], svc.flush


def _run(build, log_dir=None):
    """Run the requests of ``build()``, each inside a ``bench.request``
    span, traced into ``log_dir`` where given; then its tail, untraced."""
    import jax

    requests, tail = build()
    if log_dir is not None:
        jax.profiler.start_trace(log_dir, profiler_options=traces.options())
    try:
        out = []
        for req in requests:
            with jax.profiler.TraceAnnotation("bench.request"):
                out.append(req())
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
    return out + ([tail()] if tail is not None else [])


def _host_lines(log_dir):
    """Names of the events of each host line that holds a ``repro.`` span."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names = [e.name for e in line.events]
                if any(n.startswith("repro.") for n in names):
                    lines.append(names)
    return lines


@pytest.mark.parametrize("path", ["sweep", "tick"])
def test_program_spans_in_a_real_trace(path, tmp_path):
    """Every span of the path sits on the harness span's host line, inside a
    ``bench.request``; each reader of the path reads a finite, non-negative
    value; the results are bitwise those of the untraced run, and no trace
    counter moves."""
    build, readers, want = {
        "sweep": (_sweep_requests, SWEEP_READERS,
                  {"stage", "guard", "launch", "fetch", "assemble"}),
        "tick": (_tick_requests, TICK_READERS,
                 {"stage", "launch", "fetch", "assemble", "online.drain"}),
    }[path]
    untraced = _run(build)  # also the warm-up: every shape compiled here
    counts = harness.trace_total()
    log_dir = str(tmp_path / "trace")
    traced = _run(build, log_dir)
    assert harness.trace_total() == counts
    _same(traced, untraced)
    if path == "tick":
        assert [r is None for r in traced] == [True, False, False, False]

    lines = _host_lines(log_dir)
    assert len(lines) == 1 and "bench.request" in lines[0]
    ev = traces.load(log_dir, 1)
    requests = [(s, e) for _, n, s, e in ev.spans if n == "bench.request"]
    assert len(requests) == (2 if path == "sweep" else 3)
    ours = [(d, n, s, e) for d, n, s, e in ev.spans if n.startswith("repro.")]
    assert {n[len("repro."):].removeprefix("sweep.") for _, n, _, _ in ours} == want
    for d, n, s, e in ours:
        assert d >= 1 and any(rs <= s and e <= re_ for rs, re_ in requests), n
    if path == "tick":  # fetch and assembly of a drained tick nest in its drain
        drains = [(s, e) for _, n, s, e in ours if n == "repro.online.drain"]
        assert len(drains) == 2
        for _, n, s, e in ours:
            if n in ("repro.sweep.fetch", "repro.sweep.assemble"):
                assert any(ds <= s and e <= de for ds, de in drains), n

    ctx = harness.Context(ev, [], {})
    for name in readers:
        value = harness.reader(name).read(ctx)
        assert value is not None and math.isfinite(value) and value >= 0, name
