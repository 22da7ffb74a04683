"""``mla_flash_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the Mosaic custom calls under ``latent_attention``, the
flash kernels at the keys' width 192 with the values padded to it. An
accepted entry's ``workloads`` cannot be extended from here, so the cell
reads it under a name of its own, and this is no second implementation."""
from benchmark.layer_metrics.mla_flash_ms import read  # noqa: F401

LAYER = "Kernels"
UNIT = "ms"
