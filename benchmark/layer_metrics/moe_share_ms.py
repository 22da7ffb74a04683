"""Device time per step under the decoder's ``moe`` scope on the first
chip, forward and backward, all expert layers, in a cell whose chip holds
a share of each layer's experts (``moe_ms`` reads the same scope where
every expert is held): the block's norms, the router over all experts,
the sort and gathers of every assignment, the grouped matmuls over the
held rows, the shared expert."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.scope_ms(ctx, "moe")
