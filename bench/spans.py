"""The arithmetic of the readers of the program's own spans.

The program writes host spans named ``repro.*`` (``jax.profiler.
TraceAnnotation``) at the layer boundaries of the sweep and of the online
tick; they land on the harness's thread, inside its ``bench.request``
spans, and ``traces.load`` keeps them in ``Events.spans``.  A reader
reports a span's time per request: the summed time of the span in the
traced window over the number of ``bench.request`` spans.

A span is matched by its name up to any metadata suffix (``name#k=v#``).
A window whose requests ran while the trace holds some ``repro.`` span
but none of the one a reader asks for is an error: the span was renamed
or taken off the path.  A trace that holds no ``repro.`` span at all
comes from a program without the spans, and the reader has nothing to
read.
"""

from __future__ import annotations

from typing import Optional

from traces import Events, clipped

PREFIX = "repro."
REQUEST = "bench.request"


def base_name(name: str) -> str:
    """A span's name without its metadata suffix."""
    return name.split("#", 1)[0]


def intervals(ev: Events, name: str) -> list:
    """``(start_ns, end_ns)`` of every span called ``name``, clipped to the
    window."""
    return clipped([(s, e) for _, n, s, e in ev.spans if base_name(n) == name], ev.window)


def requests(ev: Events) -> int:
    return sum(1 for _, n, _, _ in ev.spans if n == REQUEST)


def _expect(ev: Events, name: str, found: list) -> bool:
    """Whether there is something to read: ``False`` where no request ran
    or the program writes no spans; raises where it writes spans but not
    ``name``."""
    if requests(ev) == 0 or not any(n.startswith(PREFIX) for _, n, _, _ in ev.spans):
        return False
    if not found:
        raise RuntimeError(f"the window's requests ran, but the trace holds no {name!r} "
                           "span: has it been renamed or taken off the path?")
    return True


def per_request_ms(ev: Events, name: str) -> Optional[float]:
    """Milliseconds of span ``name`` per request of the window."""
    spans = intervals(ev, name)
    if not _expect(ev, name, spans):
        return None
    return 1e-6 * sum(e - s for s, e in spans) / requests(ev)


def self_ms(ev: Events, name: str, minus: str) -> Optional[float]:
    """Milliseconds per request of span ``name`` less the spans ``minus``
    that lie inside it (its self time, where ``minus`` is its child)."""
    outer = intervals(ev, name)
    if not _expect(ev, name, outer):
        return None
    inner = intervals(ev, minus)
    total = 0.0
    for s, e in outer:
        total += (e - s) - sum(ie - i_s for i_s, ie in inner if s <= i_s and ie <= e)
    return 1e-6 * total / requests(ev)
