"""Host milliseconds per online tick spent draining a tick less the wait
for the device: the self time of the program's ``repro.online.drain``
span (summaries, ``GridResult``, threshold choice, governor) without the
``repro.sweep.fetch`` spans inside it, summed over the traced window,
over its requests."""

from spans import self_ms

SPAN = "repro.online.drain"
CHILD = "repro.sweep.fetch"


def read(ctx):
    return self_ms(ctx.events, SPAN, CHILD)
