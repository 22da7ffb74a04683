"""Device time per step under the decoder's ``eva`` scope on the first
chip, forward and backward, every EVA mixer's block whole: the block's
norm, the three projections, the rotation, both poolings of the chunks,
the flash kernels over the window's keys and over the summaries, the merge
of the two softmax states, the output projection and the residual's sum
into the float32 stream."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "eva")
