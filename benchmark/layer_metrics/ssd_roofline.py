"""Least time the chip could take for the step's state-space-duality
scans, forward and backward, over ``ssd_ms``. The least time is the
larger of 3 x (2 Q N G + 2 Q P H + 4 N P H) FLOPs a token and layer over
the bf16 peak and the least bytes (``x``, ``y``, ``dy``, ``dx`` once each,
``B``, ``C``, ``dt`` and their gradients) over HBM bandwidth
(flops_ssd.py), times the Mamba layers. At Granite 4.0-H's widths with
8,192 tokens on a v5e compute bounds it: 0.53 ms of FLOPs against 0.34 ms
of bytes a layer. Recomputation is in the time and not in the FLOPs."""
from benchmark import flops_ssd
from benchmark.layer_metrics import ssd_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = ssd_ms.read(ctx)
    shape = getattr(ctx.job, "ssd", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["d_state"], shape["groups"], shape["d_head"],
            shape["heads"])
    least_s = shape["layers"] * max(
        flops_ssd.ssd_train_flops(shape["tokens"], shape["chunk"], *dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_ssd.ssd_train_bytes(shape["tokens"], *dims,
                                  shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
