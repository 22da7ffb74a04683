"""Device time per step under the scopes ``kda_conv`` (the three causal
depth-wise convolutions of four taps and their SiLU) and ``kda_gate`` (the
L2 norms of queries and keys, the decay by channel, beta, and the output's
norm a head under its gate) on the first chip, forward and backward, every
KDA mixer: what the mixer does element by element around its scan."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "kda_conv", "kda_gate")
