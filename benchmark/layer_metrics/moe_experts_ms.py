"""Device time per step under the scope ``moe_experts`` on the first
chip, forward and backward, all layers: the three grouped matmuls over
the ragged groups and the gated activation between them."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "moe_experts")
