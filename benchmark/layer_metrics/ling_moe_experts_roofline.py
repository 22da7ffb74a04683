"""``moe_share_experts_roofline``'s reading in a cell of the
``ling-3.0-flash`` configuration: the held experts' grouped matmuls' share
of their roofline, from ``job.moe_share``'s own numbers (seven expert
layers, 16 held, d 2,560, 768). The accepted reader selects by what the job
states (``ctx.job.moe_share`` with this cell's own numbers); an accepted
entry's ``workloads`` cannot be extended from here, so the cell reads it
under a name of its own, and this is no second implementation."""
from benchmark.layer_metrics.moe_share_experts_roofline import read  # noqa: F401

LAYER = "Kernels"
UNIT = "%"
