"""Device time per step under the decoder's ``mlp`` scope on the first
chip, forward and backward, in a stack whose other layers end in the
expert layer: the leading dense layers' gated SiLU MLP with its two norms
(``gated_mlp_ms`` reads the same scope where every layer is dense)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.scope_ms(ctx, "mlp")
