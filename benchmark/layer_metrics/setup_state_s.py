"""Set-up the program spends placing state and building the step function,
less the programs jax builds meanwhile: the self time of ``state.init``,
``state.replicate``, ``state.shard``, ``state.opt`` and ``step.build``."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Entry point and host loop"
UNIT = "s"


def read(ctx):
    return setup.part("state")
