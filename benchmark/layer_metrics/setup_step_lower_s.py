"""Set-up spent lowering the step: the step module's
``jaxpr_to_mlir_module`` phase, MLIR and the Mosaic lowering of every
``pallas_call``, before the compile cache is asked."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Step program"
UNIT = "s"


def read(ctx):
    return setup.part("step_lower")
