"""Device time per step under the scopes ``moe_route`` (router matmul,
sigmoid, bias, top-k of all experts), ``moe_dispatch`` (the sort of every
assignment, held or not, and the gather of the rows) and ``moe_combine``
(the rows back to their tokens, their sum) on the first chip, forward and
backward, all expert layers, where the chip holds a share of the
experts: what the expert layer spends around its matmuls, seven eighths
of it at balance on rows that are then zeroed."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.scope_ms(ctx, "moe_route", "moe_dispatch",
                                 "moe_combine")
