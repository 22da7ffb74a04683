"""Set-up spent on every other program jax builds in the process
(initialisers, the batch, transfers, the benchmark's reference): the
self time of their trace, lowering and compile-or-cache-read phases,
wherever they hang, less those inside one of the step module's."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Entry point and host loop"
UNIT = "s"


def read(ctx):
    return setup.part("other_programs")
