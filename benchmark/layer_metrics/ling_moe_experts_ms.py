"""``moe_share_experts_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the scope ``moe_experts``, the grouped matmuls over the 16
held experts. The accepted reader selects by what the job states
(``ctx.job.moe_share`` with this cell's own numbers); an accepted entry's
``workloads`` cannot be extended from here, so the cell reads it under a
name of its own, and this is no second implementation."""
from benchmark.layer_metrics.moe_share_experts_ms import read  # noqa: F401

LAYER = "Kernels"
UNIT = "ms"
