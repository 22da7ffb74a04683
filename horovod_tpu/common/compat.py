"""The one home of the jax API spellings the ``compat-discipline`` lint
(``tools/hvdlint``) keeps out of the rest of the package.

One installation is supported (jax 0.9, docs/install.md), so every
function here is a plain forward; the indirection stays so a future jax
rename is absorbed in this file alone.
"""

from __future__ import annotations

import jax
from jax import lax as _lax

axis_size = _lax.axis_size


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def ensure_cpu_devices(n: int) -> None:
    """Size the host-CPU backend to ``n`` virtual devices (test meshes,
    virtual-mesh demos). Must run before the first device query; jax
    raises RuntimeError once the backend is initialized."""
    jax.config.update("jax_num_cpu_devices", int(n))


def pallas_tpu_compiler_params(**kwargs):
    """Build a Mosaic compiler-params object."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def distributed_is_initialized() -> bool:
    return bool(jax.distributed.is_initialized())
