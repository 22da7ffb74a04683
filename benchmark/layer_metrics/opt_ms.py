"""Device time per step under the program's ``optimizer`` scope on the
first chip: the update and the parameter write, as far as the compiler
left them instructions of their own (an update fused into a weight
gradient's fusion carries the gradient's name and counts as backward)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.class_ms(ctx, scope_reduce.OPTIMIZER)
