"""Device time per step of the Mosaic custom calls under the scope
``full_attention`` on the first chip, forward and backward, all such
layers: the flash kernels over the whole causal triangle, beside
``swa_flash_ms``'s over the window."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "full_attention")
