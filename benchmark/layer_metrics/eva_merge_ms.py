"""Device time per step under the scope ``eva_merge`` on the first chip,
forward and backward, every EVA mixer: the online-softmax combine of the
two key sets' states, the normalisation and the joint row statistics
forward; ``delta = rowsum(dO * O)`` and the sum of the two dQ backward."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "eva_merge")
