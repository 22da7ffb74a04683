"""Device time per step of the Mosaic custom calls on the first chip —
today the decoder step's only custom calls are the flash-attention
kernels (forward, dq, dk/dv; once per layer)."""
from benchmark import trace_reduce

LAYER = "Kernels"
UNIT = "ms"


def read(ctx):
    if not ctx.window:
        return None
    whole, _ = trace_reduce.matching_ns(ctx.lines, ctx.window,
                                        trace_reduce.is_mosaic_kernel)
    return ctx.per_step_ms(whole) if whole else None
