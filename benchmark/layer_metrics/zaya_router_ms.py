"""Device time per step under the scopes ``zaya_router`` (the
down-projection to the router's state, the state of the layer before
under its weight, the norm and the MLP, all float32 at the highest matmul
precision) and ``moe_route`` (softmax, the balancing bias, top-1, the
tokens per expert) on the first chip, forward and backward, every
layer."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "zaya_router", "moe_route")
