"""Device time per step under the decoder's ``head`` or ``loss`` scope on
the first chip, forward and backward, where the chip holds a share of a
deployment's tables: the final norm, the float32 logits over the rows
held here and the log-softmax over them (``head_loss_ms`` reads the same
scopes in the ``gpt2s`` cells; an entry's ``workloads`` cannot be
extended from here)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.scope_ms(ctx, "head", "loss")
