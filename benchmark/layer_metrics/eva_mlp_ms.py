"""Device time per step under the decoder's ``mlp`` scope on the first
chip, forward and backward, all layers, in the cell whose stack is EVA
mixers: the block's norm, the gated SiLU MLP of width 11,008 by blocks of
tokens (each block formed anew in the backward pass), and the residual's
sum into the float32 stream."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "eva", None):
        return None
    return scope_reduce.scope_ms(ctx, "mlp")
