"""Device time per step under the decoder's ``head`` or ``loss`` scope on
the first chip, forward and backward: the final norm, then by blocks of
tokens the float32 logits over the 131,136 rows held here
(``head_block``), each token's max, log-sum and picked logit, and in the
backward pass the block's logits again, ``softmax - onehot`` and the two
matmuls that give the hidden states' and the table's gradients
(``loss_block``); the mean over the tokens."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "head", "loss")
