"""Device time per step of the forward pass on the first chip: the
instructions whose scope path is under ``jvp(forward)`` (the program's
``forward`` scope; scope_reduce.classify)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.class_ms(ctx, scope_reduce.FORWARD)
