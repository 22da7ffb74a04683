"""ZeRO sharded training for the JAX-native API — stages 1, 2, and 3.

Beyond the reference's capability set (its DistributedOptimizer keeps the
full optimizer state on every worker): the partitioning of Rajbhandari
et al.'s ZeRO (arXiv:1910.02054), expressed TPU-natively as one compiled
SPMD program per step. The stage — ``HOROVOD_ZERO_STAGE`` / the
``zero_stage`` argument — selects how much of the training state is
partitioned 1/d across the mesh axis:

    stage 1   optimizer state + fp32 masters sharded; the full mean
              gradient is materialized on every device (per-bucket psum,
              then each device slices its own shard). Memory:
              params + grads O(P), state O(P/d).
    stage 2   gradients partitioned too (the default): each bucket's
              gradient is reduce-scattered, landing directly in its
              owning rank's shard — the full-gradient buffer never
              exists. Memory: params O(P), grads + state O(P/d).
              Numerically, psum-then-slice and psum_scatter apply the
              same reduction math, so stages 1 and 2 are bitwise equal
              on exactly-representable inputs.
    stage 3   parameters partitioned as well: the state holds NO
              replicated params (``ZeroTrainState.params`` is a
              zero-byte ``jax.ShapeDtypeStruct`` shape template), only
              the fp32 master shard. The forward pass all-gathers each
              fusion bucket's params just-in-time, in FORWARD bucket
              order (``common/fusion.forward_bucket_order`` — the
              backward-order scatter plan, run forward), with a
              depth-``HOROVOD_ZERO_PREFETCH`` prefetch chain: gather
              i's only dependence on earlier gathers is a zero-length
              anchor on gather i-(p+1), so up to p+1 gathers are in
              flight and every gather is dataflow-independent of the
              overlapped compute (XLA's latency-hiding scheduler can
              hoist them; proven by jaxpr-cone tests in
              ``tests/test_fusion_overlap.py``). The backward pass
              re-gathers under ``jax.checkpoint`` (gather outputs are
              tagged ``zero3_gather`` and excluded from the saved set),
              recomputing each bucket's params as its cotangents are
              consumed — reverse parameter order — instead of keeping
              them live across the whole backward. Gradients leave
              through the same reduce-scatter as stage 2 (it is the
              transpose of the gather). Memory: params + grads + state
              all O(P/d).

For fp32 models the stage-1/2 reduce-scatter + all-gather pair moves
exactly the same bytes as the allreduce it replaces (an allreduce IS a
reduce-scatter + all-gather). Stage 3 moves one extra gather per step
(the backward re-gather), the classic ZeRO-3 1.5x communication trade
for O(P/d) memory. For reduced-precision models (uniform bf16/fp16
params) gathers run at the model dtype — master shards are cast before
the all-gather — and only the scatter leg pays fp32 width (for
reduction precision) unless compression narrows it.

Works with any *elementwise* optax transformation (sgd, momentum, adam,
adamw, rmsprop, ...): the update runs on a flat concatenated shard, which
is elementwise-equivalent to running on the structured pytree. Transforms
that need global structure (global-norm clipping, layerwise LARS) must
stay outside or be re-derived with a psum — documented limitation.

See docs/zero.md for the stage table, memory model, prefetch schedule,
and compression composition.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from .common import faults as _faults
from .common import metrics as _metrics
from .common.compat import shard_map as _shard_map
from .common.state import AXIS_GLOBAL
from .ops import xla as _xla


class ZeroTrainState(NamedTuple):
    params: Any       # full pytree, replicated (model dtype); at stage 3
                      # a pytree of jax.ShapeDtypeStruct — the zero-byte
                      # shape template the step rebuilds layouts from
    pshard: Any       # this device's flat fp32 master-weight shard
    opt_shard: Any    # optimizer state over the master shard
    gaccum: Any       # accumulated gradient shard (None unless accumulating)
    batch_stats: Any
    step: Any
    # Fusion-bucket cap (bytes) the shard layout was built under, as a
    # replicated int32 scalar (-1 = monolithic). THE STATE OWNS THE
    # LAYOUT: make_zero_train_step reads the cap from here, so an
    # "auto"-resolved cap can never drift between init and step (e.g.
    # when the autotuner publishes a new threshold in between) — total
    # padded size alone cannot detect such drift when leaf sizes align
    # with the mesh (zero per-bucket padding).
    bucket_cap: Any = None
    # Error-feedback residuals for the compressed reduce-scatter
    # ("ef16"), sharded like gaccum (1/d per device, fp32, padded flat
    # layout). Each device keeps the quantization error of ITS OWN
    # contribution to ITS OWN output shard and re-injects it there next
    # step — the sharded-residual scheme (full per-rank residuals would
    # cost a persistent fp32 gradient copy per device, forfeiting ZeRO's
    # memory scaling; see docs/compression.md). None when the state was
    # built without error feedback; like bucket_cap, the state owns it —
    # a step resolving a different mode is rejected.
    residual: Any = None
    # ZeRO stage (1/2/3) the state was built for, as a replicated int32
    # scalar. Same state-owns-the-mode discipline as bucket_cap: the
    # stage decides what the state physically holds (stage 3 has no
    # replicated params), so the step reads it from here and a
    # mismatched explicit argument is rejected.
    stage: Any = None


def _shard_len(total: int, d: int) -> int:
    """One source of truth for the padding arithmetic: flat length padded
    up to a multiple of d, divided across the d shards."""
    return ((total + d - 1) // d * d) // d


def _resolve_stage(zero_stage) -> int:
    """Resolve the user-facing stage knob ("auto" follows
    ``HOROVOD_ZERO_STAGE``, default 2) to a validated int in {1,2,3}."""
    from .common import config as _config

    if isinstance(zero_stage, str):
        if zero_stage != "auto":
            raise ValueError(
                f"zero_stage must be 1, 2, 3, or 'auto'; got {zero_stage!r}")
        return _config.zero_stage()
    s = int(zero_stage)
    if s not in (1, 2, 3):
        raise ValueError(f"zero_stage must be 1, 2, or 3; got {s}")
    return s


def _params_are_template(params) -> bool:
    """True when every params leaf is a zero-byte ShapeDtypeStruct —
    the stage-3 representation."""
    leaves = jax.tree_util.tree_leaves(params)
    return bool(leaves) and all(
        isinstance(l, jax.ShapeDtypeStruct) for l in leaves)


class _ZeroPlan(NamedTuple):
    """Static flattening plan, generalized over fusion buckets.

    The device shard is the concatenation of per-bucket shards: bucket j
    flattens its leaves (fp32), pads to a multiple of d, reduce-scatters,
    and contributes ``bucket_padded[j] // d`` elements. With no bucket
    cap there is exactly one bucket holding every leaf in parameter
    order — the layout (and therefore every checkpointed shard) is
    bit-identical to the pre-bucketing monolithic flat. With a cap,
    buckets come from ``common/fusion.plan_buckets`` in reverse parameter
    (≈ backward-production) order, so each bucket's reduce-scatter
    depends only on its own gradients and overlaps the rest of backprop;
    the stage-3 forward walks the same buckets in forward order
    (``fusion.forward_bucket_order``) for the parameter gathers. States
    built under different caps have different shard layouts and are not
    interchangeable — rebuild (or restore via the pytree checkpoint
    path) when changing the cap.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple          # per-leaf element counts
    total: int            # sum(sizes)
    buckets: tuple        # tuple[tuple[int, ...]]: leaf indices per bucket
    bucket_elems: tuple   # unpadded element count per bucket
    bucket_padded: tuple  # padded element count per bucket (multiple of d)
    shard_len: int        # per-device shard length

    @property
    def padded(self) -> int:
        return sum(self.bucket_padded)


def _make_plan(params, d: int, bucket_cap_bytes=None) -> _ZeroPlan:
    from .common.fusion import plan_buckets

    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(l.shape for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    total = int(sum(sizes))
    if bucket_cap_bytes:
        # The wire format is fp32 regardless of model dtype (reduction
        # precision), so the planner sees fp32 byte sizes and one dtype —
        # buckets close on the byte cap only.
        buckets = tuple(
            b.indices for b in plan_buckets(
                [s * 4 for s in sizes], [jnp.float32] * len(sizes),
                bucket_cap_bytes))
    else:
        buckets = (tuple(range(len(sizes))),) if sizes else ()
    bucket_elems = tuple(sum(sizes[i] for i in idxs) for idxs in buckets)
    bucket_padded = tuple(_shard_len(n, d) * d for n in bucket_elems)
    shard_len = sum(p // d for p in bucket_padded)
    return _ZeroPlan(treedef, shapes, dtypes, sizes, total, buckets,
                     bucket_elems, bucket_padded, shard_len)


def _forward_order(plan: _ZeroPlan):
    """Bucket visit order for the stage-3 gathers: the backward-order
    plan run forward (``fusion.forward_bucket_order``)."""
    from .common.fusion import Bucket, forward_bucket_order

    return forward_bucket_order(
        [Bucket(idxs, None, 0) for idxs in plan.buckets])


def _bucket_flat_f32(leaves, plan: _ZeroPlan, j: int):
    """Bucket j's leaves as one padded fp32 flat (the scatter payload)."""
    idxs = plan.buckets[j]
    flat = (jnp.concatenate([leaves[i].astype(jnp.float32).reshape(-1)
                             for i in idxs])
            if len(idxs) > 1
            else leaves[idxs[0]].astype(jnp.float32).reshape(-1))
    pad = plan.bucket_padded[j] - plan.bucket_elems[j]
    return jnp.pad(flat, (0, pad)) if pad else flat


def _unflatten_plan(bucket_flats, plan: _ZeroPlan):
    """Rebuild the parameter pytree from per-bucket gathered flats."""
    leaves = [None] * len(plan.sizes)
    for j, idxs in enumerate(plan.buckets):
        flat = bucket_flats[j]
        off = 0
        for i in idxs:
            n = plan.sizes[i]
            leaves[i] = (flat[off:off + n].reshape(plan.shapes[i])
                         .astype(plan.dtypes[i]))
            off += n
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def _opt_state_specs(optimizer, shard_len, axis_name):
    """Per-leaf partition specs for the optimizer state over a flat
    shard: vector leaves (mu/nu/momentum, one element per parameter
    element) shard along the axis; scalar leaves (step counts) are
    replicated — identical on every device by construction."""
    shapes = jax.eval_shape(
        optimizer.init, jnp.zeros((shard_len,), jnp.float32))
    return jax.tree_util.tree_map(
        lambda s: P(axis_name) if len(s.shape) >= 1 else P(), shapes)


def _make_zero3_gather(axis_name, gather_dtype, wire, ef):
    """Build the differentiable stage-3 bucket gather.

    Forward: ``ops/xla.zero_allgather`` — an optimization_barrier pins
    the gather behind its zero-length prefetch anchor (the only ordering
    edge; see the prefetch chain in ``_build_step_fn``), then a tiled
    all_gather at ``gather_dtype``. The barrier has no differentiation
    rule, which is exactly why the gather is a ``jax.custom_vjp``: the
    primal/fwd bodies are never differentiated through, and the anchor's
    "gradient" is defined as zeros.

    Backward: the transpose of the gather is the stage-2 gradient
    reduce-scatter, so the bucket's gradient exchange IS this VJP —
    cotangents are upcast to fp32, (for ef16) the device's sharded
    residual is injected into its own segment, the payload is cast to
    the wire dtype and tiled-psum_scattered, and the reduced shard is
    upcast to fp32 (the fp32-accumulation-window discipline of
    ``ops/xla.py``). For ef16 the residual input's returned cotangent
    is defined as ``my - sent`` — the quantization error of this
    device's contribution to its own output shard — so
    ``value_and_grad`` over (pshard, residual) yields the new residual
    for free, in the same sharded layout.
    """
    if ef:
        @jax.custom_vjp
        def gather(seg, res, anchor):
            return _xla.zero_allgather(seg, axis_name, gather_dtype, anchor)

        def gather_fwd(seg, res, anchor):
            return (_xla.zero_allgather(seg, axis_name, gather_dtype, anchor),
                    (res, anchor))

        def gather_bwd(saved, cot):
            res, anchor = saved
            slen = res.shape[0]
            flat = cot.astype(jnp.float32)
            idx = lax.axis_index(axis_name)
            my = lax.dynamic_slice(flat, (idx * slen,), (slen,)) + res
            flat = lax.dynamic_update_slice(flat, my, (idx * slen,))
            payload = flat.astype(wire) if wire is not None else flat
            gseg = _xla.zero_reducescatter(flat, axis_name, wire)
            sent = lax.dynamic_slice(payload, (idx * slen,),
                                     (slen,)).astype(jnp.float32)
            return gseg, my - sent, jnp.zeros_like(anchor)

        gather.defvjp(gather_fwd, gather_bwd)
        return gather

    @jax.custom_vjp
    def gather(seg, anchor):
        return _xla.zero_allgather(seg, axis_name, gather_dtype, anchor)

    def gather_fwd(seg, anchor):
        return (_xla.zero_allgather(seg, axis_name, gather_dtype, anchor),
                anchor)

    def gather_bwd(anchor, cot):
        gseg = _xla.zero_reducescatter(
            cot.astype(jnp.float32), axis_name, wire)
        return gseg, jnp.zeros_like(anchor)

    gather.defvjp(gather_fwd, gather_bwd)
    return gather


def init_zero_train_state(model, optimizer: optax.GradientTransformation,
                          rng, sample_input, mesh,
                          axis_name: str = AXIS_GLOBAL,
                          accumulate_steps: int = 1,
                          bucket_cap_bytes="auto",
                          compression="auto",
                          zero_stage="auto") -> ZeroTrainState:
    """Initialize the ZeRO train state for the resolved stage.

    Masters and optimizer state are created per-device on that device's
    flat shard inside a shard_mapped init, so they are born sharded — no
    full fp32 copy ever exists on any one device. With
    ``accumulate_steps > 1`` a sharded gradient accumulator is added (the
    ``backward_passes_per_step`` role, still 1/d memory).

    ``zero_stage`` ("auto" follows ``HOROVOD_ZERO_STAGE``, default 2)
    is stamped into the state (``ZeroTrainState.stage``) the same way
    the bucket cap is — the state owns the mode. At stage 3 the
    replicated model-dtype params are DROPPED after the master shards
    are carved: ``state.params`` becomes a pytree of
    ``jax.ShapeDtypeStruct`` (zero bytes), and the persistent parameter
    footprint is the fp32 ``pshard`` alone. (``model.init`` still
    materializes full params transiently during this call — init-time
    only; the steady-state footprint is what stage 3 shrinks.)

    ``bucket_cap_bytes`` defines the shard layout (see ``_ZeroPlan``)
    and is recorded IN the state (``bucket_cap``); the step built by
    ``make_zero_train_step`` reads it from there, so an "auto"-resolved
    cap cannot drift between init and step even if the autotuner
    publishes a new threshold in between.

    ``compression`` (on-wire gradient format, ``common/compression.py``)
    only shapes the state through its error-feedback variant: "ef16"
    adds a sharded fp32 residual (``ZeroTrainState.residual``); fp16 and
    bf16 are stateless wire casts, so their states are identical to the
    uncompressed one. "auto" (default) follows ``HOROVOD_COMPRESSION``.
    All modes compose with every stage — at stage 3 the residual feeds
    the gather VJP's reduce-scatter (see ``_make_zero3_gather``)."""
    from .common.compression import resolve_compression
    from .common.fusion import resolve_bucket_cap

    variables = model.init(rng, sample_input, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")

    d = int(mesh.shape[axis_name])
    stage = _resolve_stage(zero_stage)
    cap = resolve_bucket_cap(bucket_cap_bytes)
    if cap is not None and cap >= 2 ** 31:
        # The cap is stamped into the state as int32 (x64-safe); a >=2GiB
        # bucket cap is indistinguishable from monolithic in practice —
        # reject it instead of overflowing deep inside init.
        raise ValueError(
            f"bucket_cap_bytes={cap} does not fit int32; use a smaller "
            f"cap (or None for monolithic fusion)")
    plan = _make_plan(params, d, cap)
    shard_len = plan.shard_len

    def init_shard(p):
        leaves = jax.tree_util.tree_leaves(p)
        idx = lax.axis_index(axis_name)
        segs = []
        for j in range(len(plan.buckets)):
            slen = plan.bucket_padded[j] // d
            segs.append(lax.dynamic_slice(
                _bucket_flat_f32(leaves, plan, j), (idx * slen,), (slen,)))
        my = jnp.concatenate(segs) if len(segs) > 1 else segs[0]
        return my, optimizer.init(my)

    sharded_init = jax.jit(_shard_map(
        init_shard, mesh, in_specs=(P(),),
        out_specs=(P(axis_name),
                   _opt_state_specs(optimizer, shard_len, axis_name)),
        check_vma=False))

    replicated = NamedSharding(mesh, P())
    params = jax.device_put(params, replicated)
    if batch_stats is not None:
        batch_stats = jax.device_put(batch_stats, replicated)
    pshard, opt_shard = sharded_init(params)

    if stage == 3:
        # Parameters live ONLY as the fp32 master shard from here on;
        # the template keeps structure/shapes/dtypes for the step's
        # plan and for gather_params without holding a single byte.
        params = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)

    def _born_sharded_zeros():
        # Born sharded, like pshard/opt_shard: materializing the full
        # padded fp32 buffer on one device first would break the "no full
        # fp32 copy on any one device" invariant exactly when it matters.
        return jax.jit(
            lambda: jnp.zeros((plan.padded,), jnp.float32),
            out_shardings=NamedSharding(mesh, P(axis_name)))()

    gaccum = None
    if accumulate_steps > 1:
        gaccum = _born_sharded_zeros()
    comp = resolve_compression(compression)
    residual = None
    if comp is not None and comp.error_feedback:
        residual = _born_sharded_zeros()
    return ZeroTrainState(params, pshard, opt_shard, gaccum, batch_stats,
                          jax.device_put(jnp.zeros((), jnp.int32),
                                         replicated),
                          jax.device_put(
                              jnp.asarray(-1 if cap is None else cap,
                                          jnp.int32), replicated),
                          residual,
                          jax.device_put(jnp.asarray(stage, jnp.int32),
                                         replicated))


def gather_params(state: ZeroTrainState, mesh,
                  axis_name: str = AXIS_GLOBAL):
    """Materialize the full parameter pytree from any ZeroTrainState.

    For stage-1/2 states this is just ``state.params`` (already
    replicated). For stage-3 states (params held as a shape template)
    the fp32 master shards are all-gathered per bucket and unflattened —
    the eval/checkpoint/export escape hatch; the train step itself never
    calls this (it gathers just-in-time inside the compiled program)."""
    if state.params is None:
        raise ValueError("state has no params (not an initialized "
                         "ZeroTrainState)")
    if not _params_are_template(state.params):
        return state.params
    if state.bucket_cap is None:
        raise ValueError(
            "stage-3 ZeroTrainState has no bucket_cap stamp — rebuild "
            "it with init_zero_train_state(...)")
    cap_raw = int(np.asarray(state.bucket_cap))
    cap = None if cap_raw < 0 else cap_raw
    d = int(mesh.shape[axis_name])
    plan = _make_plan(state.params, d, cap)

    def gather(pshard):
        flats = []
        off = 0
        for j in range(len(plan.buckets)):
            slen = plan.bucket_padded[j] // d
            flats.append(lax.all_gather(
                lax.slice_in_dim(pshard, off, off + slen),
                axis_name, tiled=True))
            off += slen
        return _unflatten_plan(flats, plan)

    fn = jax.jit(_shard_map(gather, mesh, in_specs=(P(axis_name),),
                            out_specs=P(), check_vma=False))
    return fn(state.pshard)


@_metrics.span("step.build")
def make_zero_train_step(model, optimizer: optax.GradientTransformation,
                         mesh, axis_name: str = AXIS_GLOBAL,
                         donate: bool = True, accumulate_steps: int = 1,
                         bucket_cap_bytes="auto", compression="auto",
                         zero_stage="auto", prefetch="auto"):
    """Build the jitted SPMD train step for ZeRO stage 1, 2, or 3.

    Drop-in alternative to ``training.make_train_step`` (same call
    signature on the state it builds); the loss/batch-stats semantics
    match it exactly. The stage is read from the state's stamp (see
    ``init_zero_train_state``); an explicit ``zero_stage`` here is only
    a cross-check, exactly like ``bucket_cap_bytes``.

    ``prefetch`` (stage 3 only; "auto" follows ``HOROVOD_ZERO_PREFETCH``
    or the autotuner's pinned depth, default 1) sets how many parameter
    gathers may be in flight ahead of the compute front: gather i's only
    dependence on earlier gathers is a zero-length anchor on gather
    i-(p+1). Depth 0 serializes the gathers against each other (they
    remain independent of compute); depth never changes numerics, only
    the dataflow chain — so it is autotunable for free.

    ``accumulate_steps=k`` plays the reference's
    ``backward_passes_per_step`` role: k micro-batches accumulate before
    one optimizer update. The accumulator is the already-scattered
    gradient shard, so accumulation memory stays 1/d (each micro-step
    pays one reduce-scatter — half an allreduce's bytes — and the
    all-gather only runs on update steps, when params actually change;
    at stage 3 the forward gathers run every micro-step by necessity).
    Micro-batch gradients are AVERAGED (matching this framework's
    DistributedOptimizer accumulation), not summed as the reference's
    hook accumulation effectively does — multiply the learning rate by k
    when porting a reference config that relied on summed accumulation.
    Requires a state built with the same ``accumulate_steps``.

    ``compression`` compresses the reduce-scatter leg: with fp16/bf16
    the scatter payload travels at the 16-bit wire dtype (half the
    scatter bytes of the fp32 wire) and the reduced shard is upcast to
    fp32 before the ``/d`` averaging and the optimizer update; the
    gather leg already runs at the model dtype and is unchanged. "ef16"
    additionally keeps a sharded fp32 residual in the state (see
    ``ZeroTrainState.residual``) — states with/without residuals are not
    interchangeable, and like the bucket cap, a mismatched state/step
    pair is rejected. "auto" (default) follows ``HOROVOD_COMPRESSION``
    and, for error feedback, the state: a state carrying residuals gets
    the ef16 step. At stage 3 the compressed scatter (and the residual
    update) runs inside the gather VJP — same wire bytes, same
    sharded-residual semantics."""
    from .common.compression import Compression, resolve_compression
    from .common.fusion import resolve_bucket_cap, resolve_prefetch_depth
    from .training import cross_entropy_loss

    d = int(mesh.shape[axis_name])
    k = accumulate_steps
    # THE STATE OWNS THE LAYOUT (and the stage): the effective cap and
    # stage are read from the state at call time. Explicit (non-"auto")
    # arguments here are only cross-checks against the state; "auto"
    # simply follows whatever the state was built under.
    _auto = isinstance(bucket_cap_bytes, str) and bucket_cap_bytes == "auto"
    _requested_cap = None if _auto else resolve_bucket_cap(bucket_cap_bytes)
    _auto_comp = isinstance(compression, str) and compression == "auto"
    _requested_comp = None if _auto_comp else resolve_compression(compression)
    _auto_stage = isinstance(zero_stage, str) and zero_stage == "auto"
    _requested_stage = None if _auto_stage else _resolve_stage(zero_stage)

    def _build_step_fn(plan, cap, comp, stage, pf):
        wire = comp.wire_dtype(jnp.float32) if comp is not None else None
        ef = comp is not None and comp.error_feedback
        dtypes = plan.dtypes
        # Uniform-dtype models gather at the model dtype (halving gather
        # bytes and the transient flat buffer for bf16); mixed-dtype trees
        # gather at fp32 and let _unflatten_plan cast per leaf.
        gather_dtype = (dtypes[0] if all(dt == dtypes[0] for dt in dtypes)
                        else jnp.float32)
        nb = len(plan.buckets)
        slens = [p // d for p in plan.bucket_padded]
        offs = []
        off = 0
        for s in slens:
            offs.append(off)
            off += s

        def grads_dp(state, images, labels):
            """Stages 1/2: differentiate w.r.t. the replicated params,
            then exchange gradient shards per fusion bucket."""
            @jax.named_scope("forward")
            def loss_fn(p):
                variables = {"params": p}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                    logits, updated = model.apply(
                        variables, images, train=True, mutable=["batch_stats"])
                    return (cross_entropy_loss(logits, labels),
                            updated["batch_stats"])
                logits = model.apply(variables, images, train=True)
                return cross_entropy_loss(logits, labels), None

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)

            # Mean-reduce per fusion bucket: each device leaves with its
            # shard of the global-mean gradient. Stage 2 reduce-scatters
            # (the full-gradient buffer never exists); stage 1 psums the
            # full bucket and slices its own shard — the full mean
            # gradient is live, the classic stage-1 memory shape, and
            # bitwise-identical to stage 2 for exactly-representable
            # values (same reduction math, same operands). One bucket
            # (no cap) = one collective, the original monolithic layout;
            # with a cap, bucket k's collective depends only on bucket
            # k's gradients — produced *early* in backprop (reverse
            # parameter order) — so XLA overlaps the exchange with the
            # rest of the backward pass. With compression the payload is
            # cast to the 16-bit wire dtype (that halving is the on-wire
            # saving; the flats are fp32 by construction, so one wire
            # dtype covers every bucket) and the reduced shard upcast to
            # fp32 before the /d averaging — fp32 accumulation on the
            # reduced value.
            gleaves = jax.tree_util.tree_leaves(grads)
            idx = (lax.axis_index(axis_name)
                   if (ef or stage == 1) else None)
            segs = []
            res_segs = []
            off = 0
            for j in range(nb):
                flat = _bucket_flat_f32(gleaves, plan, j)
                slen = slens[j]
                if ef:
                    # Sharded error feedback: this device's residual
                    # covers its own contribution to its own output
                    # shard — add it back into that segment before
                    # quantizing (ZeroTrainState.residual docstring).
                    my = (lax.dynamic_slice(flat, (idx * slen,), (slen,))
                          + lax.slice_in_dim(state.residual, off, off + slen))
                    flat = lax.dynamic_update_slice(flat, my, (idx * slen,))
                payload = flat.astype(wire) if wire is not None else flat
                if stage == 1:
                    full = lax.psum(payload, axis_name)
                    if wire is not None:
                        full = full.astype(jnp.float32)
                    seg = lax.dynamic_slice(full, (idx * slen,), (slen,))
                else:
                    seg = lax.psum_scatter(payload, axis_name, tiled=True)
                    if wire is not None:
                        seg = seg.astype(jnp.float32)
                segs.append(seg / d)
                if ef:
                    sent = lax.dynamic_slice(payload, (idx * slen,), (slen,))
                    res_segs.append(my - sent.astype(jnp.float32))
                off += slen
            gshard = jnp.concatenate(segs) if len(segs) > 1 else segs[0]
            new_residual = ((jnp.concatenate(res_segs)
                             if len(res_segs) > 1 else res_segs[0])
                            if ef else state.residual)
            return loss, new_stats, gshard, new_residual

        order = _forward_order(plan)
        gather = _make_zero3_gather(axis_name, gather_dtype, wire, ef)

        def grads_zero3(state, images, labels):
            """Stage 3: params exist only as the fp32 master shard.
            Differentiate w.r.t. the shard itself — the forward gathers
            each bucket just-in-time through the custom-VJP gather, and
            the VJP's reduce-scatter IS the gradient exchange (it lands
            the bucket's gradient directly in its owning shard, stage-2
            style). The whole loss runs under ``jax.checkpoint`` with
            the gather outputs excluded from the saved set, so the
            backward pass re-gathers each bucket as its cotangents come
            due (reverse parameter order) instead of holding every
            gathered bucket live across backprop."""

            @jax.named_scope("forward")
            def loss_fn(pshard, residual):
                gathered = [None] * nb
                visited = []
                for pos, j in enumerate(order):
                    seg = lax.slice_in_dim(pshard, offs[j], offs[j] + slens[j])
                    if pos > pf:
                        # The prefetch chain: a ZERO-LENGTH slice of the
                        # gather p+1 positions back is this gather's only
                        # ordering edge — no data bytes, no dependence on
                        # any compute, just "at most p+1 gathers in
                        # flight" for the scheduler.
                        anchor = lax.slice_in_dim(
                            visited[pos - pf - 1], 0, 0)
                    else:
                        anchor = jnp.zeros((0,), gather_dtype)
                    if ef:
                        res_seg = lax.slice_in_dim(
                            residual, offs[j], offs[j] + slens[j])
                        g = gather(seg, res_seg, anchor)
                    else:
                        g = gather(seg, anchor)
                    # Named so the remat policy below EXCLUDES gathered
                    # params from the saved set — the backward re-gathers
                    # instead of keeping O(P) gathered buffers alive.
                    g = checkpoint_name(g, "zero3_gather")
                    visited.append(g)
                    gathered[j] = g
                p = _unflatten_plan(gathered, plan)
                variables = {"params": p}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                    logits, updated = model.apply(
                        variables, images, train=True, mutable=["batch_stats"])
                    return (cross_entropy_loss(logits, labels),
                            updated["batch_stats"])
                logits = model.apply(variables, images, train=True)
                return cross_entropy_loss(logits, labels), None

            ckpt_loss = jax.checkpoint(
                loss_fn,
                policy=jax.checkpoint_policies.save_any_names_but_these(
                    "zero3_gather"))
            if ef:
                ((loss, new_stats),
                 (gsum, new_residual)) = jax.value_and_grad(
                     ckpt_loss, argnums=(0, 1), has_aux=True)(
                         state.pshard, state.residual)
            else:
                (loss, new_stats), gsum = jax.value_and_grad(
                    ckpt_loss, has_aux=True)(state.pshard, state.residual)
                new_residual = state.residual
            # The VJP reduce-scatter sums over ranks; average here (the
            # stage-1/2 paths divide per bucket — same value).
            return loss, new_stats, gsum / d, new_residual

        def hvd_zero_step(state: ZeroTrainState, images, labels):
            if stage == 3:
                loss, new_stats, gshard, new_residual = grads_zero3(
                    state, images, labels)
            else:
                loss, new_stats, gshard, new_residual = grads_dp(
                    state, images, labels)

            def apply_update(gshard, opt_shard, pshard):
                with jax.named_scope("optimizer"):
                    updates, new_opt = optimizer.update(gshard, opt_shard,
                                                        pshard)
                    new_pshard = optax.apply_updates(pshard, updates)
                if stage == 3:
                    # Parameters stay partitioned: no trailing gather —
                    # the NEXT step's forward gathers the fresh masters
                    # just-in-time.
                    return None, new_pshard, new_opt
                flats = []
                off = 0
                for j in range(nb):
                    seg = lax.slice_in_dim(new_pshard, off, off + slens[j])
                    flats.append(lax.all_gather(seg.astype(gather_dtype),
                                                axis_name, tiled=True))
                    off += slens[j]
                return (_unflatten_plan(flats, plan), new_pshard, new_opt)

            step = state.step + 1
            if k <= 1:
                new_params, new_pshard, new_opt = apply_update(
                    gshard, state.opt_shard, state.pshard)
                new_gaccum = state.gaccum
            else:
                acc = state.gaccum + gshard
                do_update = (step % k) == 0

                def update_branch(operand):
                    acc, opt_shard, pshard = operand
                    p, ps, op_ = apply_update(acc / k, opt_shard, pshard)
                    return p, ps, op_, jnp.zeros_like(acc)

                def skip_branch(operand):
                    acc, opt_shard, pshard = operand
                    return state.params, pshard, opt_shard, acc

                new_params, new_pshard, new_opt, new_gaccum = lax.cond(
                    do_update, update_branch, skip_branch,
                    (acc, state.opt_shard, state.pshard))

            if new_stats is not None:
                new_stats = jax.tree_util.tree_map(
                    lambda x: lax.pmean(x, axis_name), new_stats)
            loss = lax.pmean(loss, axis_name)
            return ZeroTrainState(new_params, new_pshard, new_opt, new_gaccum,
                                  new_stats, step, state.bucket_cap,
                                  new_residual, state.stage), loss

        return hvd_zero_step

    cache = {}

    def step(state: ZeroTrainState, images, labels):
        if (state.gaccum is None) != (k <= 1):
            raise ValueError(
                "state/step accumulate_steps mismatch: build the state "
                "with init_zero_train_state(..., accumulate_steps=k) "
                "matching make_zero_train_step's")
        # The layout-defining cap and the stage ride the state (init
        # stamped them); explicit arguments here must agree. The fetch
        # never blocks the train loop: bucket_cap/stage are init-time
        # arrays carried OUTSIDE the jitted program (stripped below), so
        # they are always ready — never outputs of the in-flight step.
        if state.bucket_cap is None:
            raise ValueError(
                "ZeroTrainState has no bucket_cap stamp — it was built "
                "by hand or restored without the field. Rebuild it with "
                "init_zero_train_state(...), or _replace(bucket_cap="
                "jnp.int32(-1)) if the layout is known-monolithic.")
        if state.stage is None:
            raise ValueError(
                "ZeroTrainState has no stage stamp — it was built by "
                "hand or restored from a pre-stage checkpoint. Rebuild "
                "it with init_zero_train_state(...), or _replace(stage="
                "jnp.int32(2)) if it predates stages (the historical "
                "behavior is stage 2: scattered gradients).")
        try:
            cap_raw = int(np.asarray(state.bucket_cap))
            stage = int(np.asarray(state.stage))
        except jax.errors.TracerArrayConversionError:
            raise ValueError(
                "make_zero_train_step's step function jits internally "
                "and selects the shard layout from the concrete "
                "state.bucket_cap/state.stage — call it eagerly instead "
                "of wrapping it in jax.jit (the compiled programs are "
                "exposed on step.cache for lowering/inspection)") from None
        cap = None if cap_raw < 0 else cap_raw
        if stage not in (1, 2, 3):
            raise ValueError(
                f"ZeroTrainState carries invalid stage stamp {stage}; "
                f"expected 1, 2, or 3")
        if not _auto_stage and _requested_stage != stage:
            raise ValueError(
                f"state/step ZeRO stage mismatch: the state was built "
                f"for stage {stage} but make_zero_train_step was given "
                f"zero_stage={_requested_stage}. Rebuild the state with "
                f"init_zero_train_state(..., zero_stage="
                f"{_requested_stage}) or drop the explicit argument to "
                f"follow the state.")
        is_template = _params_are_template(state.params)
        if stage == 3 and not is_template:
            raise ValueError(
                "stage-3 ZeroTrainState must hold its params as a "
                "zero-byte shape template (jax.ShapeDtypeStruct pytree) "
                "— this state carries concrete arrays, so it was built "
                "by hand or its stage stamp was forged. Rebuild it with "
                "init_zero_train_state(..., zero_stage=3).")
        if stage != 3 and is_template:
            raise ValueError(
                f"stage-{stage} ZeroTrainState must carry replicated "
                f"params, but this state holds a shape template "
                f"(stage-3 layout). Rebuild it with "
                f"init_zero_train_state(..., zero_stage={stage}).")
        # Compression follows the same state-owns-it discipline as the
        # cap: the residual's presence IS the error-feedback stamp
        # (ef16 is the only residual-carrying mode), so an "auto" step
        # adopts it; an explicit argument must agree with the state.
        if _auto_comp:
            comp = (Compression.ef16 if state.residual is not None
                    else resolve_compression("auto"))
            if (comp is not None and comp.error_feedback
                    and state.residual is None):
                raise ValueError(
                    "HOROVOD_COMPRESSION resolves to error feedback "
                    "(ef16) but this ZeroTrainState carries no residual "
                    "— it was built without it. Rebuild the state with "
                    "init_zero_train_state(..., compression='ef16') (or "
                    "under the same env) so the residual is born "
                    "sharded.")
        else:
            comp = _requested_comp
            ef_req = comp is not None and comp.error_feedback
            if ef_req != (state.residual is not None):
                mode = comp.name if comp is not None else "none"
                has = ("carries" if state.residual is not None
                       else "has no")
                raise ValueError(
                    f"state/step compression mismatch: the state {has} "
                    f"error-feedback residuals but make_zero_train_step "
                    f"was given compression={mode!r}. Rebuild the state "
                    f"with init_zero_train_state(..., "
                    f"compression={mode!r}) or pass the state's mode.")
        if not _auto and _requested_cap != cap:
            raise ValueError(
                f"state/step bucket cap mismatch: the state's shard "
                f"layout was built under bucket_cap_bytes={cap} but "
                f"make_zero_train_step was given {_requested_cap}. "
                f"Rebuild the state with init_zero_train_state(..., "
                f"bucket_cap_bytes={_requested_cap}) or drop the "
                f"explicit argument to follow the state.")
        # Prefetch depth only shapes stage-3 programs; resolve it live
        # (the autotuner may pin a new depth between steps — a changed
        # depth is a new cache key, i.e. a recompile, not a drift).
        pf = resolve_prefetch_depth(prefetch) if stage == 3 else 0
        # The optimizer-state specs depend on the shard length, which
        # depends on the parameter count — resolve per parameter-tree
        # structure and cache the compiled step under that key, so a
        # state with a different pytree (e.g. after model surgery) gets
        # its own compilation instead of an opaque shape error from a
        # stale spec.
        plan = _make_plan(state.params, d, cap)
        # Surgery on params without rebuilding the state leaves master/
        # optimizer shards sized for the OLD tree — and a state built
        # under a different bucket cap has a different shard layout; catch
        # both here with a descriptive error instead of an opaque
        # shard_map shape failure (round-2 advisor finding).
        expected_padded = plan.padded
        actual_padded = int(np.prod(state.pshard.shape))
        if actual_padded != expected_padded:
            raise ValueError(
                f"ZeroTrainState shards were built for a different "
                f"parameter tree or bucket cap: params flatten to "
                f"{plan.total} elements (padded {expected_padded} under "
                f"bucket_cap_bytes={cap}) but pshard holds "
                f"{actual_padded}. After changing either, rebuild the "
                f"state with init_zero_train_state(...) using the same "
                f"model and bucket_cap_bytes as this step instead of "
                f"reusing the old one.")
        if state.residual is not None:
            actual_res = int(np.prod(state.residual.shape))
            if actual_res != expected_padded:
                raise ValueError(
                    f"ZeroTrainState residual was built for a different "
                    f"layout: expected {expected_padded} elements under "
                    f"bucket_cap_bytes={cap}, got {actual_res}. Rebuild "
                    f"the state with init_zero_train_state(...).")
        if stage == 3:
            # Chaos seam for the partition plane: armed as a stage-3
            # step launches its gather-bearing program, so kind=raise
            # surfaces HorovodInternalError to the elastic retry loop
            # exactly where a real gather failure would
            # (docs/fault-injection.md; docs/zero.md).
            _faults.point("zero.gather")
        key = (plan.treedef, plan.shapes,
               tuple(str(dt) for dt in plan.dtypes),
               state.gaccum is None, cap,
               comp.name if comp is not None else None,
               stage, pf)
        if key not in cache:
            opt_specs = _opt_state_specs(optimizer, plan.shard_len,
                                         axis_name)
            gaccum_spec = P() if state.gaccum is None else P(axis_name)
            residual_spec = (None if state.residual is None
                             else P(axis_name))
            # bucket_cap/stage are None here: those arrays travel
            # outside the compiled program (re-attached below), so the
            # device never copies them and the host fetch above stays
            # non-blocking. At stage 3 params are None too — the
            # template is pure metadata; the program works on pshard.
            params_spec = None if stage == 3 else P()
            state_specs = ZeroTrainState(params_spec, P(axis_name),
                                         opt_specs, gaccum_spec, P(), P(),
                                         None, residual_spec, None)
            sharded = _shard_map(
                _build_step_fn(plan, cap, comp, stage, pf), mesh,
                in_specs=(state_specs, P(axis_name), P(axis_name)),
                out_specs=(state_specs, P()),
                check_vma=False)
            cache[key] = jax.jit(
                sharded, donate_argnums=(0,) if donate else ())
        cap_arr = state.bucket_cap
        stage_arr = state.stage
        template = state.params if stage == 3 else None
        inp = state._replace(bucket_cap=None, stage=None)
        if stage == 3:
            inp = inp._replace(params=None)
        new_state, loss = cache[key](inp, images, labels)
        new_state = new_state._replace(bucket_cap=cap_arr, stage=stage_arr)
        if stage == 3:
            new_state = new_state._replace(params=template)
        return new_state, loss

    step.cache = cache  # compiled programs per tree-key (introspection)
    return step
