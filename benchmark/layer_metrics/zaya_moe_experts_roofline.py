"""``moe_share_experts_roofline``'s reading in a cell of the ``zaya1-8b``
configuration: the held experts' grouped matmuls' share of their roofline,
from ``job.moe_share``'s own numbers (ten layers, 8 held, d 2,048, f 2,048,
the first step's own count of held rows). The accepted reader selects by
what the job states (``ctx.job.moe_share`` with this cell's own numbers);
an accepted entry's ``workloads`` cannot be extended from here, so the cell
reads it under a name of its own, and this is no second implementation."""
from benchmark.layer_metrics.moe_share_experts_roofline import read  # noqa: F401

LAYER = "Kernels"
UNIT = "%"
