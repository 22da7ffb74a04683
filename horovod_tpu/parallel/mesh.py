"""Parallelism mesh construction: dp / pp / sp / tp axes over TPU devices.

The reference framework is data-parallel only (SURVEY §2.5); this module is
the TPU-native extension point it anticipates: a multi-axis
``jax.sharding.Mesh`` where

- ``dp``: data parallelism (the Horovod-parity axis). Expert parallelism
  (ep) rides this axis, as in Switch/GShard-style MoE systems.
- ``pp``: pipeline stages (GPipe-style SPMD schedule,
  ``horovod_tpu.parallel.pipeline``).
- ``sp``: sequence/context parallelism — ring attention shards the sequence
  across this axis (``horovod_tpu.parallel.ring_attention``).
- ``tp``: tensor parallelism (Megatron-style sharded attention heads and
  MLP); Megatron *sequence parallelism* (norm/residual regions sharded over
  the sequence) also rides this axis.

Axis order is outer-to-inner by communication intensity: tp (most chatty)
innermost so it lands on the shortest ICI rings; dp outermost so gradient
allreduce can cross DCN.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from ..common import metrics as _metrics

AXES = ("dp", "pp", "sp", "tp")


def factor_devices(n: int, tp: Optional[int] = None, pp: Optional[int] = None,
                   sp: Optional[int] = None,
                   dp: Optional[int] = None) -> Dict[str, int]:
    """Choose axis sizes multiplying to ``n``.

    Unspecified axes are filled greedily with powers of two, preferring
    tp, then pp, then sp, and giving the remainder to dp — tiny-mesh
    defaults for dry runs; real jobs pass sizes explicitly.
    """
    fixed = {"tp": tp, "pp": pp, "sp": sp, "dp": dp}
    remaining = n
    for name, v in fixed.items():
        if v is not None:
            if remaining % v != 0:
                raise ValueError(f"{name}={v} does not divide {remaining}")
            remaining //= v
    for name in ("tp", "pp", "sp"):
        if fixed[name] is None:
            fixed[name] = 2 if remaining % 2 == 0 and remaining > 1 else 1
            remaining //= fixed[name]
    if fixed["dp"] is None:
        fixed["dp"] = remaining
        remaining = 1
    if remaining != 1:
        raise ValueError(
            f"axis sizes {fixed} do not use all {n} devices")
    return fixed


def build_parallel_mesh(devices: Sequence, tp: Optional[int] = None,
                        pp: Optional[int] = None, sp: Optional[int] = None,
                        dp: Optional[int] = None):
    """Build a 4-axis ('dp','pp','sp','tp') mesh over ``devices``."""
    from jax.sharding import Mesh

    n = len(devices)
    with _metrics.span("mesh", devices=n):
        sizes = factor_devices(n, tp=tp, pp=pp, sp=sp, dp=dp)
        arr = np.array(devices, dtype=object).reshape(
            sizes["dp"], sizes["pp"], sizes["sp"], sizes["tp"])
        return Mesh(arr, AXES)
