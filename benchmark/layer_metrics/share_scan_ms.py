"""Device time per step of the layer scans' own instructions on the first
chip, forward and backward, in a stack of several runs (one scan a run of
one mixer and feed-forward pair): ``scan_ms``'s selection (the copies
``lax.scan`` makes around its body: a stacked leaf's layer taken out, a
stacked residual's, a layer's residuals and gradients put into their
stacks) with this stack's blocks, ``sliding_attention`` and
``full_attention``, beside the ones ``scan_ms`` knows."""
from benchmark import scope_reduce
from benchmark.layer_metrics import scan_ms

LAYER = "Step program"
UNIT = "ms"

BLOCKS = scan_ms.BLOCKS | {"sliding_attention", "full_attention"}


def _of_the_scans(name, path):
    return (scope_reduce.classify(path) in (scope_reduce.FORWARD,
                                            scope_reduce.BACKWARD)
            and BLOCKS.isdisjoint(scope_reduce.segments(path))
            and path.split(";")[0].endswith(scan_ms.ENDS))


def read(ctx):
    if not getattr(ctx.job, "moe_share", None):
        return None
    return scope_reduce.per_step_ms(ctx, _of_the_scans)
