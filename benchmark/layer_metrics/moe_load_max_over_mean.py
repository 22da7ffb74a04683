"""Tokens of the busiest expert over the mean tokens an expert gets, the
largest over the layers, for the cell's batch under the seeded weights:
the runner's count (``transformer.make_router_load_fn``) before the
window. 1 is perfect balance; the grouped matmuls' longest group is this
many times the mean."""

LAYER = "Step program"
UNIT = "x"


def read(ctx):
    return getattr(ctx.job, "moe_load_max_over_mean", None)
