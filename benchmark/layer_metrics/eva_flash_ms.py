"""Device time per step of all Mosaic custom calls under the scope ``eva``
on the first chip, forward and backward, every EVA mixer: the flash
kernels over both key sets (``eva_local_flash_ms`` and
``eva_remote_flash_ms`` together)."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "eva")
