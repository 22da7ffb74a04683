"""Data-parallel training-step builder (the `DistributedOptimizer` path).

This is the TPU-native shape of the reference's training loop contract
(``examples/pytorch_synthetic_benchmark.py``): per-chip forward/backward,
gradients combined across the mesh inside one compiled program. Gradient
allreduce compiles to fused XLA AllReduces over ICI — communication overlaps
backprop automatically, subsuming the reference's background-thread fusion
cycle for the static-graph fast path (SURVEY §7 design stance).

Memory-partitioned training (ZeRO stages 1-3: sharded optimizer state,
scattered gradients, gathered-on-demand parameters) lives in ``zero.py``
and is re-exported here — ``make_zero_train_step`` is the drop-in
alternative to ``make_train_step`` when per-device memory, not compute,
bounds the model (``HOROVOD_ZERO_STAGE``; docs/zero.md).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .common import metrics as _metrics
from .common.compat import shard_map as _shard_map
from .common.fusion import exchange_compiler_options, resolve_bucket_cap
from .common.state import AXIS_GLOBAL
from .opt import DistributedOptimizer
from .zero import (  # noqa: F401  (re-export: the ZeRO step builders)
    ZeroTrainState,
    gather_params,
    init_zero_train_state,
    make_zero_train_step,
)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    batch_stats: Any
    step: Any


def cross_entropy_loss(logits, labels):
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


@_metrics.span("step.build")
def make_train_step(model, optimizer: optax.GradientTransformation,
                    mesh, axis_name: str = AXIS_GLOBAL,
                    reduce_op: Optional[int] = None,
                    donate: bool = True,
                    bucket_cap_bytes="auto",
                    compression="auto"):
    """Build a jitted SPMD train step over ``mesh``.

    Params/optimizer state are replicated; the batch is sharded along
    ``axis_name``. Batch-norm statistics are cross-chip averaged each step
    (the reference ships SyncBatchNorm for this, ``torch/sync_batch_norm.py``).

    ``bucket_cap_bytes`` is the tensor-fusion knob (see
    ``DistributedOptimizer``): ``"auto"`` (default) follows
    ``HOROVOD_FUSION_THRESHOLD``; unset, or ``None``, the gradient leaves
    are reduced where they lie and the compiler packs them into one tuple
    all-reduce. An int (or the threshold, where set) caps what one
    all-reduce instruction may hold: on a TPU the step is jitted with the
    combiner's threshold at that many bytes
    (``fusion.exchange_compiler_options``), and the compiler issues the
    all-reduces behind the backward pass as their gradients appear.

    ``compression`` is the on-wire gradient format (see
    ``DistributedOptimizer``; docs/compression.md): ``"auto"`` (default)
    follows ``HOROVOD_COMPRESSION`` and stays uncompressed — programs
    byte-identical — when that knob was never set. ``"ef16"`` keeps
    error-feedback residuals in the optimizer state: build the state
    with the same mode (``init_train_state(..., compression=...)``).
    """
    from .ops.xla import ReduceOp

    op = ReduceOp.AVERAGE if reduce_op is None else reduce_op
    dist_opt = DistributedOptimizer(optimizer, op=op, axis_name=axis_name,
                                    bucket_cap_bytes=bucket_cap_bytes,
                                    compression=compression)

    # The compiled step names its own work (docs/diagnostics.md,
    # "Tracing"): the function's name is the module's on the device
    # trace, the scopes are every instruction's ``op_name``.
    def hvd_dp_step(state: TrainState, images, labels):
        @jax.named_scope("forward")
        def loss_fn(p):
            variables = {"params": p}
            if state.batch_stats is not None:
                variables["batch_stats"] = state.batch_stats
                logits, updated = model.apply(
                    variables, images, train=True, mutable=["batch_stats"])
                return cross_entropy_loss(logits, labels), updated["batch_stats"]
            logits = model.apply(variables, images, train=True)
            return cross_entropy_loss(logits, labels), None

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        updates, new_opt_state = dist_opt.update(grads, state.opt_state,
                                                 state.params)
        with jax.named_scope("optimizer"):
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope("exchange"):
            if new_stats is not None:
                new_stats = jax.tree_util.tree_map(
                    lambda x: lax.pmean(x, axis_name), new_stats)
            loss = lax.pmean(loss, axis_name)
        return TrainState(new_params, new_opt_state, new_stats,
                          state.step + 1), loss

    n_axes = len(mesh.axis_names)
    replicated = P()
    batch_spec = P(axis_name)

    sharded_step = _shard_map(
        hvd_dp_step, mesh,
        in_specs=(replicated, batch_spec, batch_spec),
        out_specs=(replicated, replicated),
        check_vma=False,
    )
    del n_axes
    # An explicit cap is also the compiler's: see the helper's docstring.
    options = exchange_compiler_options(
        resolve_bucket_cap(bucket_cap_bytes), mesh.devices.flat[0].platform)
    return jax.jit(sharded_step, donate_argnums=(0,) if donate else (),
                   compiler_options=options or None)


def init_train_state(model, optimizer, rng, sample_input,
                     compression="auto") -> TrainState:
    """``compression`` must match the step's (``make_train_step``): the
    error-feedback mode ("ef16") adds fp32 residuals to the optimizer
    state, so init and step have to agree on the state pytree. Both
    default to "auto" (the ``HOROVOD_COMPRESSION`` env), which agrees by
    construction."""
    with _metrics.span("state.init") as setup:
        variables = model.init(rng, sample_input, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats")
        dist_opt = DistributedOptimizer(optimizer, compression=compression)
        opt_state = dist_opt.init(params)
        state = TrainState(params, opt_state, batch_stats,
                           jnp.zeros((), dtype=jnp.int32))
        setup.add(**_metrics.tree_counts(state))
        return state


def replicate_state(state: TrainState, mesh) -> TrainState:
    sharding = NamedSharding(mesh, P())
    with _metrics.span("state.replicate", **_metrics.tree_counts(state)):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), state)


def init_opt_state(optimizer: optax.GradientTransformation, params, mesh,
                   zero_axis: Optional[str] = None):
    """Optimizer state with mesh-consistent shardings.

    ``zero_axis="dp"`` additionally shards every moment leaf over that
    mesh axis (ZeRO-1 memory partitioning composed with whatever
    model-parallel sharding the param already has): the first unsharded
    dimension divisible by the axis size gets the axis; leaves with no
    such dimension stay as-is (partial ZeRO). Pair with
    ``make_train_step(..., opt_shardings=...)`` so the compiled step
    keeps the moments sharded instead of replicating them back.

    ``jax.jit(optimizer.init)(params)`` commits EVERY output leaf to a
    single device (no out_shardings → XLA's default assignment) — a
    state that happens to step (jit re-shards it) but poisons a
    checkpoint template: an orbax restore faithfully reproduces the
    single-device placement, and the restored state then mixes
    single-device and full-mesh committed arrays in the next step, which
    jax rejects. Eager ``optimizer.init`` instead builds moments with
    ``zeros_like`` — inheriting each param's NamedSharding — and this
    helper re-places the remaining scalar leaves (e.g. Adam's ``count``)
    as mesh-replicated, so every leaf is mesh-consistent.
    """
    replicated = NamedSharding(mesh, P())
    zero_size = int(mesh.shape[zero_axis]) if zero_axis else 0

    def place(leaf):
        if getattr(leaf, "ndim", None) == 0:
            return jax.device_put(leaf, replicated)
        if not zero_axis or zero_size <= 1:
            return leaf
        if not hasattr(leaf, "sharding"):
            return leaf  # host (numpy) leaf: nothing to partition
        # Extend the leaf's inherited (param) spec with the zero axis on
        # the first unsharded, divisible dimension.
        spec = list(getattr(leaf.sharding, "spec", ()) or ())
        spec += [None] * (leaf.ndim - len(spec))
        for i, (dim, cur) in enumerate(zip(leaf.shape, spec)):
            if cur is None and dim % zero_size == 0 and dim >= zero_size:
                spec[i] = zero_axis
                return jax.device_put(leaf, NamedSharding(mesh, P(*spec)))
        return leaf  # no divisible dim: this leaf stays un-partitioned

    with _metrics.span("state.opt") as setup:
        state = jax.tree_util.tree_map(place, optimizer.init(params))
        setup.add(**_metrics.tree_counts(state))
        return state


def shard_batch(batch, mesh, axis_name: str = AXIS_GLOBAL):
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)
