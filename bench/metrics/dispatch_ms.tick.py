"""Host milliseconds per online tick spent dispatching the sweep program
(argument transfer, sharding of the arguments, the jitted call's
enqueue): the program's ``repro.sweep.launch`` span summed over the
traced window, over its requests."""

from spans import per_request_ms

SPAN = "repro.sweep.launch"


def read(ctx):
    return per_request_ms(ctx.events, SPAN)
