"""Device time per step under the scopes ``moe_route`` (router matmul,
softmax, top-k, the two loss terms), ``moe_dispatch`` (sort by expert,
gather of the rows) and ``moe_combine`` (gate weighting, the rows back to
their tokens) on the first chip, forward and backward, all layers: what
the expert layer spends around its matmuls."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "moe_route", "moe_dispatch",
                                 "moe_combine")
