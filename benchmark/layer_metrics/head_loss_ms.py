"""Device time per step under the decoder's ``head`` or ``loss`` scope on
the first chip, forward and backward: the final norm, the float32 logits
and the log-softmax over the vocabulary."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "head", "loss")
