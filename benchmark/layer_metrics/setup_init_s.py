"""Set-up spent in ``hvd.init()`` and the mesh: the self time of the spans
``init``, ``engine.start`` (less the native core under it) and ``mesh``
(``hvd.init`` builds one; a decoder job opens ``mesh`` alone, in
``build_parallel_mesh``)."""
from benchmark.layer_metrics import setup_in_program_s as setup

LAYER = "Entry point and host loop"
UNIT = "s"


def read(ctx):
    return setup.part("init")
