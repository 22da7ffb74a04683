"""Least time the chip could take for the step's delta-rule scans, forward
and backward, over ``kda_scan_ms``. The least time is the larger of 18 B H
T K V FLOPs a mixer (the recurrence's three products, forward and both
gradients) over the bf16 peak and the least bytes (q, k, v, the decay, beta
and o once forward; those, do and the five gradients once backward) over
HBM bandwidth (flops_ling.py), times the KDA mixers. At B 1, H 32, T
16,384, K = V = 128 on a v5e the bytes bound it: 0.79 ms of FLOPs against
2.79 ms of bytes a mixer. The chunked form's tiles and solve, the states
kept for the backward pass and recomputation are in the time and not in
the count."""
from benchmark import flops_ling
from benchmark.layer_metrics import kda_scan_ms

LAYER = "Kernels"
UNIT = "%"


def read(ctx):
    ms = kda_scan_ms.read(ctx)
    shape = getattr(ctx.job, "kda", None)
    if not ms or not shape or not ctx.peaks:
        return None
    dims = (shape["batch"], shape["heads"], shape["seq_len"],
            shape["k_dim"], shape["v_dim"])
    least_s = shape["layers"] * max(
        flops_ling.kda_scan_train_flops(*dims)
        / ctx.peaks["bf16_flops_per_s"],
        flops_ling.kda_scan_train_bytes(*dims, shape["itemsize"])
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms / 1e3)
