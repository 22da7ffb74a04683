"""Least time the chip could take for the EVA mixers' kernels over both
key sets, forward and backward, over ``eva_flash_ms``. The least time is
the larger of 14 B H D FLOPs a visible pair (seven matmuls; the pairs of
the exact and of the summary set together) over the bf16 peak and twelve
[B, H, T, D] and six [B, H, T / C, D] arrays over HBM bandwidth
(flops_eva.py), times the mixers a step runs. At B 1, 32 heads of 128,
T 32,768, W 2,048, C 16 on a v5e compute bounds it: 18.9 ms of FLOPs
against 4.1 ms of bytes a mixer."""
from benchmark import flops_eva
from benchmark.layer_metrics import eva_flash_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = eva_flash_ms.read(ctx)
    shape = getattr(ctx.job, "eva", None)
    if not ms or not shape or not ctx.peaks:
        return None
    least_s = shape["layers"] * max(
        flops_eva.eva_flash_train_flops(
            shape["batch"], shape["heads"], shape["head_dim"],
            shape["seq_len"], shape["window"], shape["chunk"])
        / ctx.peaks["bf16_flops_per_s"],
        flops_eva.eva_flash_train_bytes(
            shape["batch"], shape["heads"], shape["head_dim"],
            shape["seq_len"], shape["chunk"], shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
