"""Least time the chip could take for the tied head's slice, forward and
backward, over ``zaya_head_loss_ms``: 6 d rows FLOPs a token (the logits,
the gradient by the hidden states and by the table; flops_zaya.py) over
the bf16 peak. The block-wise head forms every block's logits a second
time in the backward pass, which is in the time and not in the FLOPs, so
three quarters is the most this can read; its operands are float32."""
from benchmark import flops_zaya
from benchmark.layer_metrics import zaya_head_loss_ms

LAYER = "Step program"
UNIT = "%"


def read(ctx):
    ms = zaya_head_loss_ms.read(ctx)
    shape = getattr(ctx.job, "zaya_head", None)
    if not ms or not shape or not ctx.peaks:
        return None
    least_s = flops_zaya.head_train_flops(
        shape["tokens"], shape["d"], shape["vocab_rows"]) \
        / ctx.peaks["bf16_flops_per_s"]
    return 100 * least_s / (ms / 1e3)
