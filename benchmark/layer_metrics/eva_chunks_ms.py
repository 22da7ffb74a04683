"""Device time per step under the scope ``eva_chunks`` on the first chip,
forward and backward, every EVA mixer: both poolings, a chunk's keys under
``softmax(mu . k)`` and its values under ``softmax(phi . k)``, float32,
formed anew in the backward pass."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "eva_chunks")
