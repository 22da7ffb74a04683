"""Device time per step of the Mosaic custom calls under the scope
``sliding_attention`` on the first chip, forward and backward, all such
layers: the flash kernels where they cull the tiles beyond the window."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "sliding_attention")
