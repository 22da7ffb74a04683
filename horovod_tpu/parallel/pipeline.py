"""SPMD pipeline parallelism (GPipe schedule via collective_permute).

Each ``pp`` mesh-axis member holds one stage's parameters (stage params are
sharded over ``pp``). The schedule runs ``M + S - 1`` ticks; at each tick
every stage applies itself to its current activation, then activations shift
one hop around the ring (``lax.ppermute``) — stage 0 injects a fresh
microbatch each of the first ``M`` ticks, the last stage emits a finished
microbatch from tick ``S-1`` on. Autodiff through the scan + ppermute gives
the backward pipeline for free (ppermute's transpose is the reverse
permute), so one ``jax.grad`` over the whole thing yields a correct
1F1B-equivalent-cost GPipe backward.

The reference has no pipeline support (SURVEY §2.5) — this is part of the
TPU build's parallelism surface beyond DP parity.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..common.compat import axis_size as _axis_size


def spmd_pipeline(stage_fn: Callable, stage_params, microbatches,
                  axis_name: str = "pp", collect_fn: Callable = None):
    """Run ``microbatches`` through the pipeline.

    stage_fn(params, x) -> y : applies ONE stage (same structure in/out).
    stage_params: this member's stage parameters (already pp-local).
    microbatches: [M, ...] stacked microbatch activations — a single
    array or any pytree of [M, ...] leaves (e.g. the decoder's
    ``transformer.Carry(x, seg, stats, state)``: per-microbatch side
    data rides the activation ring with the activations). Stage-0 input
    layout; other stages ignore the values and receive via the ring.

    collect_fn(y) selects the sub-pytree that is actually an OUTPUT
    (e.g. ``lambda carry: carry._replace(seg=None, state=None)``: a
    member that is None has no leaf); defaults to the whole structure.
    Side data the stages merely pass through (segment ids) still rides
    the per-tick ring carry — later stages consume it — but is excluded
    from the per-tick output collect and the closing psum-broadcast,
    saving a dynamic-update per tick and collective bandwidth per leaf.

    Returns ``collect_fn``-selected [M, ...] outputs as produced by the
    LAST stage (valid on every member after the closing psum-broadcast).
    """
    tmap = jax.tree_util.tree_map
    S = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    T = M + S - 1
    if collect_fn is None:
        collect_fn = lambda y: y  # noqa: E731

    fwd = [(i, (i + 1) % S) for i in range(S)]
    x0 = tmap(lambda m: jnp.zeros_like(m[0]), microbatches)
    outbuf = tmap(jnp.zeros_like, collect_fn(microbatches))

    def tick(carry, t):
        state, outbuf = carry
        # stage 0 injects microbatch t (clamped; masked when t >= M)
        mb = tmap(lambda m: lax.dynamic_index_in_dim(
            m, jnp.clip(t, 0, M - 1), 0, keepdims=False), microbatches)
        inject = jnp.logical_and(stage == 0, t < M)
        state = tmap(lambda m, s: jnp.where(inject, m, s), mb, state)
        y = stage_fn(stage_params, state)
        # last stage collects finished microbatch t-(S-1)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        collect = jnp.logical_and(stage == S - 1, t >= S - 1)

        def collect_leaf(ob, yy):
            cur = lax.dynamic_index_in_dim(ob, out_idx, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                ob, jnp.where(collect, yy, cur), out_idx, 0)

        outbuf = tmap(collect_leaf, outbuf, collect_fn(y))
        state = tmap(lambda yy: lax.ppermute(yy, axis_name, fwd), y)
        return (state, outbuf), None

    (_, outbuf), _ = lax.scan(tick, (x0, outbuf), jnp.arange(T))
    # Broadcast the last stage's outputs to all pp members so downstream
    # (loss) code is uniform SPMD.
    outbuf = tmap(
        lambda ob: lax.psum(
            jnp.where(stage == S - 1, ob, jnp.zeros_like(ob)), axis_name),
        outbuf)
    return outbuf
