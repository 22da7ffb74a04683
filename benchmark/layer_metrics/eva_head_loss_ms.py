"""Device time per step under the decoder's ``head`` or ``loss`` scope on
the first chip, forward and backward, in the cell whose stack is EVA
mixers: the final norm, the float32 logits of the eight prediction heads
(one matrix of 8 x 320 columns), every position's cross-entropy under
each head and its hand-written backward."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "eva", None):
        return None
    return scope_reduce.scope_ms(ctx, "head", "loss")
