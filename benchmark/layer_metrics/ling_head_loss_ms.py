"""``lat_head_loss_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the scopes ``head`` and ``loss`` outside ``mtp``, the final
norm, the float32 logits over the 19,648 rows held here and the main
cross-entropy. An accepted entry's ``workloads`` cannot be extended from
here, so the cell reads it under a name of its own, and this is no second
implementation."""
from benchmark.layer_metrics.lat_head_loss_ms import read  # noqa: F401

LAYER = "Step program"
UNIT = "ms"
