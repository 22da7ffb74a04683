"""The part of ``allreduce_ms`` during which no other operation ran on
that chip: the exchange nothing hides."""
from benchmark import trace_reduce

LAYER = "Data-parallel step"
UNIT = "ms"


def read(ctx):
    if not ctx.window or ctx.chips < 2:
        return None
    _, exposed = trace_reduce.matching_ns(ctx.lines, ctx.window,
                                          trace_reduce.is_all_reduce)
    return ctx.per_step_ms(exposed)
