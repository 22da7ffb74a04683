"""Least time the chip could take for the latent mixers' causal
attention, forward and backward, over ``mla_flash_ms``. The least time is
the larger of 7 B H T^2 D FLOPs a mixer over the bf16 peak and twelve [B,
H, T, D] arrays over HBM bandwidth (flops_glm_lite.py), times the mixers
a step runs (the multi-token-prediction module's among them). At B 2,
H 20, T 8,192, D 256 on a v5e compute bounds it: 24.4 ms of FLOPs against
2.5 ms of bytes a mixer."""
from benchmark import flops_glm_lite
from benchmark.layer_metrics import mla_flash_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = mla_flash_ms.read(ctx)
    shape = getattr(ctx.job, "mla", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["batch"], shape["heads"], shape["seq_len"],
            shape["head_dim"])
    least_s = shape["layers"] * max(
        flops_glm_lite.latent_flash_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_glm_lite.latent_flash_train_bytes(*dims, shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
