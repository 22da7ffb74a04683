"""Median host time for one ``step(...)`` call of the traced window to
return, on the host's clock around the call."""
import statistics

LAYER = "Entry point and host loop"
UNIT = "ms"


def read(ctx):
    if not ctx.dispatch_s:
        return None
    return 1e3 * statistics.median(ctx.dispatch_s)
