"""Least time the chip could take for the grouped matmuls over the held
experts, forward and backward, over ``moe_share_experts_ms``. The least
time is the larger of 18 rows_held d f FLOPs a layer over the bf16 peak
and the least bytes (the held experts' matrices four times, five
[rows_held, d] arrays) over HBM bandwidth (flops_afmoe.py), with
``rows_held`` the run's own count of the assignments that fell on held
experts in the first step, the mean over the expert layers: not k T / 8.
At 16 experts of 2,048 x 1,024 and 16,384 rows on a v5e compute bounds it:
3.1 ms of FLOPs against 1.4 ms of bytes a layer."""
from benchmark import flops_afmoe
from benchmark.layer_metrics import moe_share_experts_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = moe_share_experts_ms.read(ctx)
    shape = getattr(ctx.job, "moe_share", None)
    if not ms or not shape or not shape.get("rows_held") or not ctx.peaks:
        return None
    dims = (shape["rows_held"], shape["d"], shape["d_expert"])
    least_s = shape["layers"] * max(
        flops_afmoe.held_matmul_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_afmoe.held_matmul_train_bytes(
            *dims, shape["experts_held"], shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
