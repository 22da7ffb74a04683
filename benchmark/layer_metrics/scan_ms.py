"""Device time per step of the layer scan's own instructions on the first
chip, forward and backward: the leaf operations under the ``forward``
scope in either direction and under none of the decoder's blocks
(``embed``, ``attention``, ``mlp``, ``moe``, ``head``, ``loss``) whose
path ends in ``while/body/squeeze`` (a stacked leaf's layer taken out),
``dynamic_slice`` (a stacked residual's) or ``dynamic_update_slice`` (a
layer's residuals and gradients put into their stacks): the copies
``lax.scan`` makes around its body, which no block's metric times."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"

BLOCKS = {"embed", "attention", "mlp", "moe", "head", "loss"}
ENDS = ("while/body/squeeze", "dynamic_slice", "dynamic_update_slice")


def _of_the_scan(name, path):
    return (scope_reduce.classify(path) in (scope_reduce.FORWARD,
                                            scope_reduce.BACKWARD)
            and BLOCKS.isdisjoint(scope_reduce.segments(path))
            and path.split(";")[0].endswith(ENDS))


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _of_the_scan)
