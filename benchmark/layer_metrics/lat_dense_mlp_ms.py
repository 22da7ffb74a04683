"""Device time per step under the decoder's ``mlp`` scope and outside
``mtp`` on the first chip, forward and backward: the one leading dense
layer's gated SiLU MLP of 10,240 with its norm (``share_dense_mlp_ms``
reads the scope in ``trinity-mini-t8192``; a multi-token-prediction
module whose layer ended in a dense MLP would be ``mtp_ms``'s)."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def _the_stacks_mlp(name, path):
    scopes = scope_reduce.segments(path)
    return "mlp" in scopes and "mtp" not in scopes


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _the_stacks_mlp)
