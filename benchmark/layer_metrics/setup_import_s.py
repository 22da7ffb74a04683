"""Set-up spent importing the program: the self time of the ``import:*``
spans (``horovod_tpu``, ``horovod_tpu.models``,
``horovod_tpu.models.transformer``, ``horovod_tpu.ops.ssd``), each from
the first to the last of its import statements, the packages jax and flax
bring in after the runner's own imports among them."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Entry point and host loop"
UNIT = "s"


def read(ctx):
    return setup.part("import")
