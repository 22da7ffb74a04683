"""Device time per step of the Mosaic custom calls under the scope
``eva_remote`` on the first chip, forward and backward, every EVA mixer:
the flash kernels of every query over the chunk summaries under the
block-causal rule, visible tiles only."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "eva_remote")
