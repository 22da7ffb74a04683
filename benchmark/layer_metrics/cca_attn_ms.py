"""Device time per step under the decoder's ``cca`` scope on the first
chip, forward and backward, every CCA mixer's block whole: the block's
norm, the three projections into the compressed space, the values' shift,
both convolutions, the q-k mean, the q/k norm with its temperature, the
rotation, the flash kernels at 8 over 2 heads, the output projection and
the scaled residual."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "cca")
