"""Device time per step under the decoder's ``head`` or ``loss`` scope
and outside ``mtp`` on the first chip, forward and backward: the final
norm, the float32 logits over the rows held here and the main
cross-entropy (``share_head_loss_ms`` reads both scopes wherever they
are; the multi-token-prediction module's own head and loss are
``mtp_head_loss_ms``)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def _the_main_head(name, path):
    scopes = set(scope_reduce.segments(path))
    return "mtp" not in scopes and not scopes.isdisjoint(("head", "loss"))


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _the_main_head)
