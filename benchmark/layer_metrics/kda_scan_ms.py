"""Device time per step of the Mosaic custom calls under the scope ``kda``
on the first chip, forward and backward, every KDA mixer: the scan's
Pallas kernels (``kda_fwd``, ``kda_bwd``; ``ops/kda.py``), the forward
kernel a second time where a rematerialized layer runs it again. Nothing
where the scan runs as XLA's loop over chunks."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "kda")
