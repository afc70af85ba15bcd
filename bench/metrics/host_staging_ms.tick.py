"""Host milliseconds per online tick spent staging the sweep's draws (the
NHPP candidate streams of the threshold cells and the per-row columns):
the program's ``repro.sweep.stage`` span summed over the traced window,
over its requests."""

from spans import per_request_ms

SPAN = "repro.sweep.stage"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
