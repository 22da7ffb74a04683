"""Device time per step under the decoder's ``kda`` scope on the first
chip, forward and backward, every KDA mixer's block: the block's norm, the
projections, the three convolutions, the norms and gates, the scan (its
kernels ``kda_fwd`` and ``kda_bwd`` and what XLA does around them), the
output projection and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "kda")
