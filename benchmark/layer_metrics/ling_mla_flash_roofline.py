"""Least time the chip could take for the latent mixers' causal attention,
forward and backward, over ``ling_mla_flash_ms``, at the widths the
mathematics has: queries and keys of 192, values of 128. The least time is
the larger of B H T^2 (4 x 192 + 3 x 128) FLOPs a mixer over the bf16 peak
and twelve [B, H, T, .] arrays at their own widths over HBM bandwidth
(flops_ling.py), times the mixers a step runs (the multi-token-prediction
module's among them). The kernels take one width, so the values ride zeros
from 128 to 192: that work is in the time and not in the count, and shows
as a lower share. At B 1, H 32, T 16,384 on a v5e compute bounds it: 50.2
ms of FLOPs against 2.5 ms of bytes a mixer."""
from benchmark import flops_ling
from benchmark.layer_metrics import ling_mla_flash_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = ling_mla_flash_ms.read(ctx)
    shape = getattr(ctx.job, "ling_mla", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["batch"], shape["heads"], shape["seq_len"],
            shape["qk_dim"], shape["v_dim"])
    least_s = shape["layers"] * max(
        flops_ling.latent_flash_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_ling.latent_flash_train_bytes(*dims, shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
