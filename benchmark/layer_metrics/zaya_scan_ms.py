"""Device time per step of the layer scan's own instructions on the first
chip, forward and backward, in this stack's one run (CCA + experts, one
scan): ``scan_ms``'s selection (the copies ``lax.scan`` makes around its
body: a layer's leaves taken out of their stacks, the residual stream and
the router's carried state stacked forward and sliced backward, gradients
stacked back) with this stack's block, ``cca``, beside the ones
``scan_ms`` knows."""
from benchmark import scope_reduce
from benchmark.layer_metrics import scan_ms

LAYER = "Step program"
UNIT = "ms"

BLOCKS = scan_ms.BLOCKS | {"cca"}


def _of_the_scan(name, path):
    return (scope_reduce.classify(path) in (scope_reduce.FORWARD,
                                            scope_reduce.BACKWARD)
            and BLOCKS.isdisjoint(scope_reduce.segments(path))
            and path.split(";")[0].endswith(scan_ms.ENDS))


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _of_the_scan)
