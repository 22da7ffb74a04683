"""Least time the chip could take for the step's grouped expert matmuls,
forward and backward, over ``moe_experts_ms``. The least time is the
larger of 18 k T d f FLOPs a layer over the bf16 peak and the least
bytes (every expert's matrices four times, the gathered rows five) over
HBM bandwidth (flops_moe.py). At OLMoE's widths with 8,192 tokens on a
v5e compute bounds it: 12.6 ms of FLOPs against 5.6 ms of bytes a
layer."""
from benchmark import flops_moe
from benchmark.layer_metrics import moe_experts_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = moe_experts_ms.read(ctx)
    shape = getattr(ctx.job, "moe", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["tokens"], shape["experts_per_token"], shape["d"],
            shape["d_expert"])
    least_s = shape["layers"] * max(
        flops_moe.grouped_matmul_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_moe.grouped_matmul_train_bytes(
            *dims, shape["n_experts"], shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
