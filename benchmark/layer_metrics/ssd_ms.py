"""Device time per step under the scope ``ssd`` on the first chip,
forward and backward, all Mamba-2 layers: the chunked state-space-duality
scan (``ops/ssd.py``) with what its backward pass computes anew."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "ssd")
