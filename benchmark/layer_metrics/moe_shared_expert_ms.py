"""Device time per step under the scope ``moe_shared`` on the first chip,
forward and backward, all expert layers: the shared expert, a gated SiLU
MLP every token passes beside its routed experts."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "moe_shared")
