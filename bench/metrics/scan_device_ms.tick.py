"""Device milliseconds per tick of the float64 scan program
(``simulate_sweep`` of ``core/simulator.py``): the summed duration of the
program's runs on the device in the traced window over the ticks in it."""

from traces import module_seconds

PROGRAM = "simulate_sweep"


def read(ctx):
    ticks = sum(1 for s in ctx.events.spans if s[1] == "bench.tick")
    secs = module_seconds(ctx.events, lambda name: PROGRAM in name)
    if ticks == 0 or secs <= 0:
        return None
    return 1e3 * secs / ticks
