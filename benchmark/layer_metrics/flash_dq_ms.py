"""Device time per step of the Mosaic custom calls under the scope
``flash_dq`` on the first chip: the flash-attention dq kernel, all
layers."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "flash_dq")
