"""Device time per step under the decoder's ``mlp`` scope on the first
chip, forward and backward, all layers."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "mlp")
