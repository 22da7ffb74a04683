"""Median duration of the step program's event on the first chip's
``XLA Modules`` line."""

LAYER = "Step program"
UNIT = "ms"


def read(ctx):
    return ctx.step_device_ms()
