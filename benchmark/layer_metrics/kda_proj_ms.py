"""Device time per step under the scopes ``kda_proj`` (the queries', keys'
and values' projection as one matmul, the decay's, the output gate's and
beta's) and ``kda_out`` (the output projection) on the first chip, forward
and backward, every KDA mixer: the mixer's matmuls with the model's
width."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "kda_proj", "kda_out")
