"""Device time per step of the Mosaic custom calls under the scope
``flash_fwd`` on the first chip: the flash-attention forward kernel, all
layers."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "flash_fwd")
