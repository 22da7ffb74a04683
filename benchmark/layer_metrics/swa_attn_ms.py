"""Device time per step under the decoder's ``sliding_attention`` scope on
the first chip, forward and backward, all such layers: the block's norm,
the projections, per-head QK-norm and RoPE, the K/V heads repeated for the
kernels, the flash kernels with their window, the gate, the output
projection, the post-norm and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "sliding_attention")
