"""Device time per step of the Mosaic custom calls under the scope
``eva_local`` on the first chip, forward and backward, every EVA mixer:
the flash kernels causal inside a window, the windows as batch entries."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "eva_local")
