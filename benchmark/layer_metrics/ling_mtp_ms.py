"""``mtp_ms``'s reading in a cell of the ``ling-3.0-flash`` configuration:
the scope ``mtp``, the multi-token-prediction module whole (its two norms,
the labels' embedding and the projection of both halves, its layer
``mtp/latent_attention`` and ``mtp/moe``, its head over the rows held and
its cross-entropy). An accepted entry's ``workloads`` cannot be extended
from here, so the cell reads it under a name of its own, and this is no
second implementation."""
from benchmark.layer_metrics.mtp_ms import read  # noqa: F401

LAYER = "Step program"
UNIT = "ms"
