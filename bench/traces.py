"""Profiler traces: capture a window, reduce it to events, and the
arithmetic the per-layer metric readers share.

``capture`` wraps a window in ``jax.profiler`` (the Python tracer off,
so the host side holds the runtime's own events and the harness's
``TraceAnnotation`` spans).  ``load`` turns the ``.xplane.pb`` it writes
into a plain ``Events`` record, which is all a metric reader sees:

* ``ops``: device operations, ``(device, name, start_ns, end_ns)``;
* ``modules``: compiled programs run on a device, same layout;
* ``spans``: host events on the harness's thread, ``(depth, name,
  start_ns, end_ns)``, the harness's own spans (``bench.*``) among them;
* ``window``: ``(start_ns, end_ns)`` of the traced window.

``Events`` also round-trips through JSON, so a trimmed recording can be
kept with the tests.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import List, Optional, Tuple

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    ops: List[Tuple[int, str, float, float]]
    modules: List[Tuple[int, str, float, float]]
    spans: List[Tuple[int, str, float, float]]
    window: Tuple[float, float]
    n_devices: int

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(dataclasses.asdict(self), f)

    @classmethod
    def from_json(cls, path: str) -> "Events":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(
            ops=[tuple(x) for x in d["ops"]],
            modules=[tuple(x) for x in d["modules"]],
            spans=[tuple(x) for x in d["spans"]],
            window=tuple(d["window"]),
            n_devices=int(d["n_devices"]),
        )


def options():
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _device_index(plane_name: str) -> Optional[int]:
    # "/device:TPU:0" -> 0; the TPU's own planes only
    if not plane_name.startswith("/device:TPU:"):
        return None
    tail = plane_name[len("/device:TPU:") :]
    return int(tail) if tail.isdigit() else None


def load(log_dir: str, n_devices: int) -> Events:
    """Reduce the trace written under ``log_dir`` to ``Events``.

    Device operations are the events of each TPU plane's "XLA Ops" line,
    programs those of its "XLA Modules" line.  Host spans are the events
    of the host thread that carries the harness's ``bench.`` spans, with
    their nesting depth.  The window is the extent of the harness's
    outermost spans.
    """
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, modules, spans = [], [], []
    for plane in data.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(dev, e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(dev, e.name, e.start_ns, e.end_ns) for e in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if not any(e.name.startswith(SPAN_PREFIX) for e in events):
                continue
            spans += _with_depth(events)
    tops = [s for s in spans if s[1].startswith(SPAN_PREFIX)]
    if not tops:
        raise RuntimeError("the trace holds none of the harness's spans")
    window = (min(s[2] for s in tops), max(s[3] for s in tops))
    return Events(ops=ops, modules=modules, spans=spans, window=window, n_devices=n_devices)


def _with_depth(events) -> list:
    """Host events of one thread with their nesting depth."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        while stack and stack[-1] <= e.start_ns:
            stack.pop()
        out.append((len(stack), e.name, e.start_ns, e.end_ns))
        stack.append(e.end_ns)
    return out


# ---- the arithmetic the metric readers share


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def clipped(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_s(ev: Events) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the devices used;
    ``None`` where no device operation was recorded."""
    if not ev.ops:
        return None
    per = [
        union_ns(clipped([(s, e) for d, _, s, e in ev.ops if d == dev], ev.window))
        for dev in range(ev.n_devices)
    ]
    return sum(per) / len(per) / 1e9


def window_s(ev: Events) -> float:
    return (ev.window[1] - ev.window[0]) / 1e9


def idle_share(ev: Events) -> Optional[float]:
    """Per cent of the window in which no operation ran on a device,
    averaged over the devices used."""
    busy = busy_s(ev)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / window_s(ev))


def op_seconds(ev: Events, match) -> float:
    """Device seconds of the operations whose name ``match`` accepts,
    summed over devices."""
    return sum(e - s for _, name, s, e in ev.ops if match(name)) / 1e9


def kernel_seconds(ev: Events, kernel: str, launcher: str) -> float:
    """Device seconds of a Pallas kernel: the operations named after it,
    or, where the trace names none so, the custom calls that run inside
    the programs of its launcher (``jit_<launcher>``)."""
    secs = op_seconds(ev, lambda name: kernel in name)
    if secs > 0:
        return secs
    spans = [(d, s, e) for d, name, s, e in ev.modules if launcher in name]
    return sum(
        e - s
        for d, name, s, e in ev.ops
        if "custom-call" in name and any(d == md and ms <= s and e <= me for md, ms, me in spans)
    ) / 1e9


def module_seconds(ev: Events, match) -> float:
    return sum(e - s for _, name, s, e in ev.modules if match(name)) / 1e9


def top_ops(ev: Events, n: int = 10) -> list:
    """The device operations that took most time, summed by name."""
    tot: dict = {}
    for _, name, s, e in ev.ops:
        tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(ev: Events, n: int = 10) -> list:
    """The longest stretches in which device 0 ran nothing, each named by
    the innermost host event under way at its middle."""
    busy = sorted(clipped([(s, e) for d, _, s, e in ev.ops if d == 0], ev.window))
    gaps, t = [], ev.window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if ev.window[1] > t:
        gaps.append((t, ev.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for lo, hi in gaps[:n]:
        mid = 0.5 * (lo + hi)
        under = [sp for sp in ev.spans if sp[2] <= mid < sp[3]]
        name = max(under)[1] if under else "between spans"
        if under and not name.startswith(SPAN_PREFIX):
            outer = min(under)[1]
            name = f"{outer} > {name}"
        out.append([name, (hi - lo) / 1e9])
    return out
