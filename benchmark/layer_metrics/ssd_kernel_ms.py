"""Device time per step of the Mosaic custom calls under the scope ``ssd``
on the first chip, forward and backward, all Mamba-2 layers: the scan's
Pallas kernels (``ssd_fwd``, ``ssd_bwd``; ``ops/ssd.py``). Nothing where
the scan runs as XLA einsums; over ``ssd_ms`` it is the share of the scan
that the kernels are."""
from benchmark import scope_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "ssd")
