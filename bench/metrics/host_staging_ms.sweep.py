"""Host milliseconds per sweep request spent staging the draws: the
program's ``repro.sweep.stage`` span (the draw-cell loop that builds the
``[rows, steps]`` draw stacks, its enqueued device programs, and the
per-row columns) summed over the traced window, over its requests."""

from spans import per_request_ms

SPAN = "repro.sweep.stage"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
