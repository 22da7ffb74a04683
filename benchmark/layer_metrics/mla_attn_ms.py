"""Device time per step under the decoder's ``latent_attention`` scope on
the first chip, forward and backward, every latent mixer's block (the
stack's and the multi-token-prediction module's): the block's norm, both
low-rank chains with their norms and rotations, the key's assembly over
the heads, the flash kernels at the one head width, the output
projection and the residual add."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "latent_attention")
