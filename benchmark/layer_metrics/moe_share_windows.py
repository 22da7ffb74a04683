"""The most windows of the sorted assignments an expert layer took in the
last timed step (``parallel/moe.py``; the step's ``readings["windows"]``,
which the runner keeps on the job): 1 while the rows of the held experts
fit one window of twice their share at balance, more when the routing
has moved so far that the layer fell back to further windows, each a
second pass over the section. Nothing for a job whose step returns no
such reading."""
import numpy as np

LAYER = "Step program"
UNIT = "x"


def read(ctx):
    windows = (getattr(ctx.job, "readings", None) or {}).get("windows")
    return None if windows is None else int(np.max(np.asarray(windows)))
