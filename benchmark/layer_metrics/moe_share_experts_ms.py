"""Device time per step under the scope ``moe_experts`` on the first chip,
forward and backward, all expert layers, where the chip holds a share of
the experts: the grouped matmuls over the held experts' groups, the gated
product, the masks of the rows no group covers."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.scope_ms(ctx, "moe_experts")
