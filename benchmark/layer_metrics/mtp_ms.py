"""Device time per step under the scope ``mtp`` on the first chip,
forward and backward: the multi-token-prediction module whole, its two
norms, the labels' embedding and the projection of both halves, its
layer (``mtp/latent_attention``, ``mtp/moe``), its head over the rows
held and its cross-entropy."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "mtp")
