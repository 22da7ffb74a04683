"""Least time the chip could take for the sliding layers' windowed
attention, forward and backward, over ``swa_flash_ms``. The least time is
the larger of 14 B H D pairs(T, W) FLOPs a layer over the bf16 peak and
twelve [B, H, T, D] arrays over HBM bandwidth (flops_afmoe.py:
``flops.py``'s convention with the attended pairs in place of T^2 / 2).
At B 2, H 32, T 8,192, D 128, W 2,048 on a v5e compute bounds it: 8.5 ms
of FLOPs against 2.0 ms of bytes a layer."""
from benchmark import flops_afmoe
from benchmark.layer_metrics import swa_flash_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = swa_flash_ms.read(ctx)
    shape = getattr(ctx.job, "swa", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["batch"], shape["heads"], shape["seq_len"],
            shape["head_dim"])
    least_s = shape["layers"] * max(
        flops_afmoe.attention_train_flops(*dims, shape["window"])
        / ctx.peaks["bf16_flops_per_s"],
        flops_afmoe.attention_train_bytes(*dims, shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
