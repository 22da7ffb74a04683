"""Device time per step in ``all-reduce`` operations on the first chip."""
from benchmark import trace_reduce

LAYER = "Data-parallel step"
UNIT = "ms"


def read(ctx):
    if not ctx.window or ctx.chips < 2:
        return None
    whole, _ = trace_reduce.matching_ns(ctx.lines, ctx.window,
                                        trace_reduce.is_all_reduce)
    return ctx.per_step_ms(whole)
