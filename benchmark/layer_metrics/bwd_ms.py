"""Device time per step of the backward pass on the first chip: the
instructions whose scope path is under ``transpose(jvp(forward))`` (the
program's ``forward`` scope, transposed; scope_reduce.classify)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.class_ms(ctx, scope_reduce.BACKWARD)
