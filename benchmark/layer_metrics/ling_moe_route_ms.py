"""``moe_share_route_ms``'s reading in a cell of the ``ling-3.0-flash``
configuration: the scopes ``moe_route`` (512 scores a token, the groups'
scores, the four kept, the top-8), ``moe_dispatch`` and ``moe_combine``.
The accepted reader selects by what the job states (``ctx.job.moe_share``
with this cell's own numbers); an accepted entry's ``workloads`` cannot be
extended from here, so the cell reads it under a name of its own, and this
is no second implementation."""
from benchmark.layer_metrics.moe_share_route_ms import read  # noqa: F401

LAYER = "Step program"
UNIT = "ms"
