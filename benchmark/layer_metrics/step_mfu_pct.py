"""The configuration's model FLOPs per step and chip over
``step_device_ms`` over the chip's published bf16 peak (causal attention
counted at half; recomputation not counted)."""

LAYER = "Step program"
UNIT = "%"


def read(ctx):
    ms = ctx.step_device_ms()
    if not ms or not ctx.peaks:
        return None
    per_chip = ctx.job.model_flops_per_step / ctx.chips
    return 100 * per_chip / (ms / 1e3) / ctx.peaks["bf16_flops_per_s"]
