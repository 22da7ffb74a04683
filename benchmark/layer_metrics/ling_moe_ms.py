"""``moe_share_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the decoder's ``moe`` scope, every expert layer, the multi-
token-prediction module's among them. The accepted reader selects by what
the job states (``ctx.job.moe_share`` with this cell's own numbers); an
accepted entry's ``workloads`` cannot be extended from here, so the cell
reads it under a name of its own, and this is no second implementation."""
from benchmark.layer_metrics.moe_share_ms import read  # noqa: F401

LAYER = "Step program"
UNIT = "ms"
