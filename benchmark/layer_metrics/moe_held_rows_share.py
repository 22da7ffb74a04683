"""Assignments that fell on held experts over all assignments, over the
expert layers, for the cell's batch under the seeded weights: the
runner's count from the first step's own tokens per expert, before the
window. With an eighth of the experts held it is 0.125 at balance; the
grouped matmuls do this share of a whole layer's work, and the sort and
the gathers handle every assignment all the same."""

LAYER = "Step program"
UNIT = "share"


def read(ctx):
    return getattr(ctx.job, "moe_held_rows_share", None)
