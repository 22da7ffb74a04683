"""Device time per step under the scopes ``eva_qkv`` (the fused
projection of queries, keys and values and the rotation of the first two)
and ``eva_out`` (the output projection) on the first chip, forward and
backward, every EVA mixer: the four d x d matmuls of a mixer."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "eva_qkv", "eva_out")
