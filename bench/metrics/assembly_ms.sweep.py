"""Host milliseconds per sweep request spent assembling the result: the
program's ``repro.sweep.assemble`` spans (the per-cell
``SimulationSummary`` loop and the ``GridResult`` grids) summed over the
traced window, over its requests."""

from spans import per_request_ms

SPAN = "repro.sweep.assemble"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
