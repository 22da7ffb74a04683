"""1 - union of the intervals in which an operation ran on the first
chip over the steady traced window (first step's start to last step's
end)."""
from benchmark import trace_reduce

LAYER = "Device"
UNIT = "%"


def read(ctx):
    if not ctx.window:
        return None
    busy = trace_reduce.busy_ns(ctx.lines, ctx.window)
    return 100 * (1 - busy / (ctx.window[1] - ctx.window[0]))
