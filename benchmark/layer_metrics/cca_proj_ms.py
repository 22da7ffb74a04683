"""Device time per step under the scopes ``cca_q`` (the queries'
projection), ``cca_kv`` (the keys' and the values') and ``cca_out`` (the
output projection) on the first chip, forward and backward, every CCA
mixer: the four matmuls between the stream's width and the compressed
space."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "cca_q", "cca_kv", "cca_out")
