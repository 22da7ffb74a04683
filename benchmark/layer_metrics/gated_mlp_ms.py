"""Device time per step under the decoder's ``mlp`` scope on the first
chip, forward and backward, all layers, in the cells whose dense
feed-forward block is the gated SiLU one (``mlp_ms`` reads the same scope
in the ``gpt2s`` cells, whose block is the two-matrix GELU one)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "gated_mlp", False):
        return None
    return scope_reduce.scope_ms(ctx, "mlp")
