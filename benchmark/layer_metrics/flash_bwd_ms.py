"""Device time per step of the Mosaic custom calls under the scope
``flash_bwd`` on the first chip: the fused flash-attention backward (dQ,
dK and dV from one walk of the tiles), all layers. Nothing where a
program has no such kernel: the two passes ``flash_dq`` and ``flash_dkv``
run instead (a parent before the kernel, or a sequence too long for
it)."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "flash_bwd")
