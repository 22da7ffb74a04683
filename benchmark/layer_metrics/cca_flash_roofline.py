"""Least time the chip could take for the CCA mixers' causal attention,
forward and backward, over ``cca_flash_ms``. The least time is the larger
of 7 B Hq T^2 D FLOPs a mixer over the bf16 peak and six [B, Hq, T, D] and
six [B, Hkv, T, D] arrays over HBM bandwidth (flops_zaya.py), times the
mixers a step runs. At B 1, 8 over 2 heads, T 16,384, D 128 on a v5e
compute bounds it: 9.8 ms of FLOPs against 0.3 ms of bytes a mixer."""
from benchmark import flops_zaya
from benchmark.layer_metrics import cca_flash_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = cca_flash_ms.read(ctx)
    shape = getattr(ctx.job, "cca", None)
    if not ms or not shape or not ctx.peaks:
        return None
    least_s = shape["layers"] * max(
        flops_zaya.cca_flash_train_flops(
            shape["batch"], shape["heads"], shape["seq_len"],
            shape["head_dim"]) / ctx.peaks["bf16_flops_per_s"],
        flops_zaya.cca_flash_train_bytes(
            shape["batch"], shape["heads"], shape["kv_heads"],
            shape["seq_len"], shape["head_dim"], shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
