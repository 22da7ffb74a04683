"""Device time per step of the layer scans' own instructions on the first
chip, forward and backward, in this stack's four runs (KDA + dense, KDA +
experts, latent + experts, KDA + experts: one ``lax.scan`` a run):
``scan_ms``'s selection (the copies ``lax.scan`` makes around its body)
with this stack's blocks, ``kda``, ``latent_attention`` and ``mtp`` (which
no scan runs), beside the ones ``scan_ms`` knows."""
from benchmark import scope_reduce
from benchmark.layer_metrics import scan_ms

LAYER = "Step program"
UNIT = "ms"

BLOCKS = scan_ms.BLOCKS | {"kda", "latent_attention", "mtp"}


def _of_the_scans(name, path):
    return (scope_reduce.classify(path) in (scope_reduce.FORWARD,
                                            scope_reduce.BACKWARD)
            and BLOCKS.isdisjoint(scope_reduce.segments(path))
            and path.split(";")[0].endswith(scan_ms.ENDS))


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _of_the_scans)
