"""Device time per step under the decoder's ``moe`` scope on the first
chip, forward and backward, all layers: the block's norm, the router,
dispatch, the grouped expert matmuls, combine and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "moe")
