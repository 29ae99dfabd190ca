"""Share of the measured window in which no operation ran on the device,
from the profiler trace (1 - union of device-op intervals / window), in a
cell whose window drives session verbs back to back."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return ctx.trace.idle_pct
