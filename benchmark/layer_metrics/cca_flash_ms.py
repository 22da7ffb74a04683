"""Device time per step of the Mosaic custom calls under the scope ``cca``
on the first chip, forward and backward, every CCA mixer: the flash
kernels at 8 query heads over 2 key/value heads of 128."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "cca")
