"""Set-up spent on the native core: the span ``native.load`` with its
children ``native.make_q`` (the freshness check) and ``native.build``
(the build from ``csrc/`` in a fresh checkout; ``built`` is 1 on the
span). Nothing for a job that never calls ``hvd.init``."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Entry point and host loop"
UNIT = "s"


def read(ctx):
    return setup.part("native_core")
