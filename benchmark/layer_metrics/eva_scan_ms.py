"""Device time per step of the layer scan's own instructions on the first
chip, forward and backward, in this stack's one run (EVA + MLP, one scan):
``scan_ms``'s selection (the copies ``lax.scan`` makes around its body: a
layer's leaves taken out of their stacks, the float32 stream stacked
forward and sliced backward, gradients stacked back) with this stack's
block, ``eva``, beside the ones ``scan_ms`` knows."""
from benchmark import scope_reduce
from benchmark.layer_metrics import scan_ms

LAYER = "Step program"
UNIT = "ms"

BLOCKS = scan_ms.BLOCKS | {"eva"}


def _of_the_scan(name, path):
    return (scope_reduce.classify(path) in (scope_reduce.FORWARD,
                                            scope_reduce.BACKWARD)
            and BLOCKS.isdisjoint(scope_reduce.segments(path))
            and path.split(";")[0].endswith(scan_ms.ENDS))


def read(ctx):
    if not getattr(ctx.job, "eva", None):
        return None
    return scope_reduce.per_step_ms(ctx, _of_the_scan)
