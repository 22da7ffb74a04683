"""Device time per step under the scope ``router_bias`` on the first
chip: the train step's move of the router's balancing bias from the
tokens each expert got, outside the optimizer."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return scope_reduce.scope_ms(ctx, "router_bias")
