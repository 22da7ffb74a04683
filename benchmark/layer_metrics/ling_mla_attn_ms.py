"""``mla_attn_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the decoder's ``latent_attention`` scope, the stack's one
latent mixer and the multi-token-prediction module's (the query's one
matrix, the key/value chain with its norm, both QK-norms and rotations, the
flash kernels with the values padded to the keys' width, the gate a head,
the output projection). An accepted entry's ``workloads`` cannot be
extended from here, so the cell reads it under a name of its own, and this
is no second implementation."""
from benchmark.layer_metrics.mla_attn_ms import read  # noqa: F401

LAYER = "Step program"
UNIT = "ms"
