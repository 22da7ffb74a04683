"""Device time per step under the decoder's ``full_attention`` scope on
the first chip, forward and backward, all such layers: as
``swa_attn_ms`` without the rotation and with the kernels over the whole
causal triangle."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "full_attention")
