"""Share of the traced window, in percent, in which no operation ran on
the device: 1 - (union of device-busy intervals / window)."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * trace.idle_share(ctx.trace)
