"""Device time per step under the scopes ``cca_conv`` (the values' shift
by one token, the depthwise convolution and the convolution by head, on
queries and keys), ``cca_qk_mean`` (the q-k mean across a group) and
``cca_norm`` (the float32 q/k norm with its temperature, the rotation of
half a head) on the first chip, forward and backward, every CCA mixer:
everything of the mixer that is neither a projection nor a kernel."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "cca_conv", "cca_qk_mean", "cca_norm")
