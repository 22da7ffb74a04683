"""DistributedOptimizer for the JAX-native API.

Parity target: ``hvd.DistributedOptimizer`` (reference
``torch/optimizer.py:31-195``, ``tensorflow/__init__.py:383-444``), rebuilt
for the JAX/optax idiom: instead of hooking per-parameter gradient
accumulators, we wrap the optax ``GradientTransformation`` so that
``update()`` allreduces the gradient pytree across the mesh axis before the
inner optimizer sees it. Inside ``jit``/``shard_map`` the allreduce compiles
to one tuple XLA AllReduce over the gradient leaves where they lie, over
ICI — tensor fusion falls out of compilation (XLA's all-reduce combiner)
rather than a background fusion buffer.

``backward_passes_per_step`` (gradient accumulation before communication,
reference ``torch/optimizer.py:46``) is supported via
``optax.MultiSteps``-style accumulation handled by the caller or the
``accumulate`` knob here.

This wrapper keeps params, grads, and optimizer state fully replicated —
the right trade when memory is not the constraint. When it is, the ZeRO
plane (``zero.py``, ``HOROVOD_ZERO_STAGE={1,2,3}``) shards state, then
gradients, then parameters 1/d across the mesh while keeping this
module's compression and fusion semantics (docs/zero.md).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import optax

from .common.state import AXIS_GLOBAL
from .ops import xla as _xla


class DistributedState(NamedTuple):
    inner_state: Any
    accum: Any
    step: Any
    # Error-feedback residuals (fp32, one per parameter element) when the
    # compression mode carries error feedback ("ef16"); None otherwise —
    # a None child adds no leaves, so uncompressed states and compiled
    # programs are unchanged by the field's existence.
    residual: Any = None


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    op: int = _xla.ReduceOp.AVERAGE,
    axis_name: str = AXIS_GLOBAL,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    backward_passes_per_step: int = 1,
    compression="auto",
    bucket_cap_bytes="auto",
) -> optax.GradientTransformation:
    """Wrap ``optimizer`` so updates are computed from mesh-reduced grads.

    Must be used inside a program where ``axis_name`` is bound (shard_map /
    pjit over ``hvd.mesh()``); single-device programs may simply not bind
    the axis and pass ``axis_name=None`` to skip communication.

    ``bucket_cap_bytes`` is the tensor-fusion knob (``common/fusion.py``):
    ``"auto"`` (default) follows ``HOROVOD_FUSION_THRESHOLD`` — the same
    knob that paces the host plane's cycle fusion, including its autotuned
    value — and is ``None`` when the knob was never set: the leaves are
    all-reduced where they lie and the compiler packs them into one tuple
    all-reduce. An int caps Adasum's launch groups here; for sum and
    average a bucket cannot be told from its leaves below XLA, and the cap
    takes effect as the compiler's combiner threshold:
    ``make_train_step`` passes it on a TPU, and a step jitted by hand
    does with ``jax.jit(..., compiler_options=
    fusion.exchange_compiler_options(cap, "tpu"))``.

    ``compression`` selects the on-wire gradient format
    (``common/compression.py``; docs/compression.md):
    ``hvd.Compression.{none,fp16,bf16,ef16}``, the mode name as a
    string, or ``"auto"`` (default) to follow ``HOROVOD_COMPRESSION`` —
    unset keeps programs byte-identical to the uncompressed path. With
    fp16/bf16 the bucketed AllReduces reduce in the 16-bit wire dtype
    (≈2x fewer wire bytes for fp32 grads) with fp32 post-reduction
    arithmetic; ``ef16`` additionally keeps fp32 residuals in this
    transformation's state (``DistributedState.residual``) so
    quantization error is re-injected next step (error feedback) instead
    of biasing the trajectory. The residual makes the state pytree
    differ from the uncompressed one — init and update must agree on the
    mode (``init_train_state`` / ``make_train_step`` plumb it through).
    """
    import jax.numpy as jnp

    from .common.compression import (apply_error_feedback, init_residual,
                                     resolve_compression)
    from .common.fusion import resolve_bucket_cap

    cap = resolve_bucket_cap(bucket_cap_bytes)
    comp = resolve_compression(compression)
    ef = comp is not None and comp.error_feedback
    wire_comp = comp.inner if ef else comp

    def reduce_grads(grads):
        if axis_name is None:
            return grads
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        reduced = _xla.grouped_allreduce(
            leaves, axis_name=axis_name, op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            bucket_cap_bytes=cap,
            compression=wire_comp,
        )
        return jax.tree_util.tree_unflatten(treedef, reduced)

    def check_residual(state):
        """Fail loudly on an init/update compression mismatch (the ZeRO
        plane's state-owns-the-mode contract, applied here): a residual
        structure mismatch would otherwise surface as an opaque pytree
        error (ef step, plain state) or silently drop the error
        feedback (plain step, ef state)."""
        residual = getattr(state, "residual", None)
        if ef and residual is None:
            raise ValueError(
                "compression mismatch: this DistributedOptimizer was "
                "built with error feedback (ef16) but the optimizer "
                "state carries no residuals. Initialize the state with "
                "the same compression mode (init_train_state(..., "
                "compression='ef16') / DistributedOptimizer(..., "
                "compression='ef16').init).")
        if not ef and residual is not None:
            raise ValueError(
                "compression mismatch: the optimizer state carries "
                "error-feedback residuals but this DistributedOptimizer "
                "was built without error feedback. Build init and "
                "update with the same compression mode.")

    def reduce_grads_ef(grads, residual):
        """(reduced, new_residual): correct with the residual, quantize,
        reduce in the wire dtype, store back the quantization error."""
        if axis_name is None:
            return grads, residual
        wire, new_res = apply_error_feedback(comp, grads, residual)
        reduced = reduce_grads(wire)
        # grouped_allreduce returns each leaf at its (wire) input dtype;
        # hand the inner optimizer gradients at the original dtype.
        reduced = jax.tree_util.tree_map(
            lambda r, g: r.astype(g.dtype), reduced, grads)
        return reduced, new_res

    if backward_passes_per_step <= 1:

        def init_fn(params):
            return DistributedState(optimizer.init(params), None, None,
                                    init_residual(params) if ef else None)

        def update_fn(grads, state, params=None, **extra):
            check_residual(state)
            with jax.named_scope("exchange"):
                if ef:
                    grads, new_res = reduce_grads_ef(grads, state.residual)
                else:
                    grads, new_res = reduce_grads(grads), None
            with jax.named_scope("optimizer"):
                updates, inner = optimizer.update(grads, state.inner_state,
                                                  params, **extra)
            return updates, DistributedState(inner, None, None, new_res)

        return optax.GradientTransformation(init_fn, update_fn)

    # Gradient accumulation: communicate only every k-th step (parity:
    # backward_passes_per_step, reference torch/optimizer.py:46,119-135).
    k = backward_passes_per_step

    def init_fn(params):
        accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        return DistributedState(optimizer.init(params), accum,
                                jnp.zeros((), dtype=jnp.int32),
                                init_residual(params) if ef else None)

    def update_fn(grads, state, params=None, **extra):
        check_residual(state)
        accum = jax.tree_util.tree_map(lambda a, g: a + g, state.accum, grads)
        step = state.step + 1
        do_comm = step >= k

        def comm_branch(operand):
            accum, inner_state, residual = operand
            mean = jax.tree_util.tree_map(lambda a: a / k, accum)
            with jax.named_scope("exchange"):
                if ef:
                    # Error feedback at communication time: the residual
                    # corrects what actually travels the wire (the k-step
                    # mean), untouched on skipped micro-steps.
                    reduced, new_res = reduce_grads_ef(mean, residual)
                else:
                    reduced, new_res = reduce_grads(mean), residual
            with jax.named_scope("optimizer"):
                updates, inner = optimizer.update(reduced, inner_state,
                                                  params, **extra)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return (updates, inner, zeros, jnp.zeros((), dtype=jnp.int32),
                    new_res)

        def skip_branch(operand):
            accum, inner_state, residual = operand
            updates = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return updates, inner_state, accum, step, residual

        updates, inner, accum, step, resid = jax.lax.cond(
            do_comm, comm_branch, skip_branch,
            (accum, state.inner_state, state.residual))
        return updates, DistributedState(inner, accum, step, resid)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedGradientTransformation(*args, **kwargs):
    """Alias matching JAX naming conventions."""
    return DistributedOptimizer(*args, **kwargs)
