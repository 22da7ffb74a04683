"""Device time per step under the decoder's ``attention`` scope on the
first chip, forward and backward, all layers: the block's norm, the
projections (and QK-norm and RoPE where the model has them), the flash
kernels, the output projection and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "attention")
