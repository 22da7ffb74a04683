"""Tokens of the busiest *held* expert over the mean tokens a held expert
gets, the largest over the expert layers, for the cell's batch under the
seeded weights: the runner's count from the first step's own tokens per
expert, before the window. 1 is perfect balance among the held experts;
the grouped matmuls' longest group is this many times their mean
(``moe_load_max_over_mean`` is the same over all experts where all are
held)."""

LAYER = "Step program"
UNIT = "x"


def read(ctx):
    return getattr(ctx.job, "moe_held_max_over_mean", None)
