"""Device time per step under ``mtp`` and under ``head`` or ``loss``
inside it on the first chip, forward and backward: the
multi-token-prediction module's own norm, its float32 logits through the
model's head over the rows held here and its cross-entropy of the token
after the next."""
from benchmark import scope_reduce

LAYER = "Step program"
UNIT = "ms"


def _the_modules_head(name, path):
    scopes = set(scope_reduce.segments(path))
    return "mtp" in scopes and not scopes.isdisjoint(("head", "loss"))


def read(ctx):
    return scope_reduce.per_step_ms(ctx, _the_modules_head)
