"""Device time per step under the decoder's ``mamba`` scope on the first
chip, forward and backward, all Mamba-2 layers: the block's norm, the
in-projection, the convolution, the scan, the gated norm, the
out-projection and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "mamba")
