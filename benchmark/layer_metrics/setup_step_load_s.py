"""Set-up spent in the step module's ``backend_compile`` phase: the cache
key, the read and the executable's load when the cache holds it
(``cache_hit`` 1 on the span), the compile when it does not."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Step program"
UNIT = "s"


def read(ctx):
    return setup.part("step_load")
