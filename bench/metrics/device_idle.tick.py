"""Per cent of the traced window in which no operation ran on a device,
averaged over the devices the cell uses: 1 - (union of the device
operations' intervals) / window."""

from traces import idle_share


def read(ctx):
    return idle_share(ctx.events)
