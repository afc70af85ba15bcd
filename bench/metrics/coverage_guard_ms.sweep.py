"""Host milliseconds per sweep request in the block sweep's coverage
guard: the program's ``repro.sweep.guard`` span (the staged gap stack
copied to the host and summed in float64) summed over the traced window,
over its requests."""

from spans import per_request_ms

SPAN = "repro.sweep.guard"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
