"""Least time the chip could take for the step's causal attention,
forward and backward, over ``flash_attn_ms``. The least time is the
larger of 7 B H T^2 D FLOPs per layer over the bf16 peak and twelve
[B, H, T, D] arrays over HBM bandwidth (flops.py). On a v5e with D 64 in
bf16 compute bounds it above T of about 820 and bandwidth below: the
t1024 cell is compute-bound, the t128 cell bandwidth-bound."""
from benchmark import flops
from benchmark.layer_metrics import flash_attn_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = flash_attn_ms.read(ctx)
    shape = getattr(ctx.job, "attention", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["batch"], shape["heads"], shape["seq_len"],
            shape["head_dim"])
    least_s = shape["layers"] * max(
        flops.causal_attention_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops.causal_attention_train_bytes(*dims, shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
