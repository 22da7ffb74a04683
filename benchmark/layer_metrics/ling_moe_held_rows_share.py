"""``moe_held_rows_share``'s reading in a cell of the ``ling-3.0-flash``
configuration: the assignments that fell on held experts over all, the
runner's count from the first step (1/64 at balance). The accepted reader
selects by what the job states (``ctx.job.moe_share`` with this cell's own
numbers); an accepted entry's ``workloads`` cannot be extended from here,
so the cell reads it under a name of its own, and this is no second
implementation."""
from benchmark.layer_metrics.moe_held_rows_share import read  # noqa: F401

LAYER = "Step program"
UNIT = "share"
