"""Device time per step of the Mosaic custom calls under the scope
``latent_attention`` on the first chip, forward and backward, every
latent mixer: the flash kernels at the head width q, k and v share."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "latent_attention")
