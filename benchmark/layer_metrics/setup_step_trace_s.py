"""Set-up spent tracing the step: the step module's ``jaxpr_trace`` phase
with everything inside it (the traces of the ``jit``s the step calls,
every ``pallas_call`` body among them)."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Step program"
UNIT = "s"


def read(ctx):
    return setup.part("step_trace")
