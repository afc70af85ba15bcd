"""Host milliseconds per online tick spent waiting on the device and
copying the sweep's accumulators back: the program's
``repro.sweep.fetch`` span summed over the traced window, over its
requests."""

from spans import per_request_ms

SPAN = "repro.sweep.fetch"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
