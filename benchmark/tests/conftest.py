"""benchmark/tests run on the CPU backend with four virtual devices:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They check the yardstick's arithmetic and rehearse the harness at tiny
sizes; a device number comes only from a chip run."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
