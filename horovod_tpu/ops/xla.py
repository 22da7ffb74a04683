"""In-jit functional collectives — the XLA/ICI backend.

This is the TPU-native replacement for the reference's NCCL op layer
(``ops/nccl_operations.cc``): instead of host-driven ``ncclAllReduce`` calls
on private streams, collectives are *compiled into the program* as XLA HLO
(AllReduce/AllGather/ReduceScatter/CollectivePermute) and scheduled by XLA
over ICI with near-optimal compute/communication overlap (SURVEY §7 design
stance).

Use these inside ``jax.shard_map`` / ``pjit`` with a bound mesh axis::

    @partial(jax.shard_map, mesh=mesh, in_specs=P('hvd'), out_specs=P('hvd'))
    def step(batch):
        ...
        grads = hvd.xla.allreduce(grads, op=hvd.Average)

The eager API (``horovod_tpu.ops.eager``) builds on these same primitives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..common.compat import axis_size as _axis_size

from ..common.state import AXIS_CROSS, AXIS_GLOBAL, AXIS_LOCAL


class ReduceOp:
    """Reduction op ids (parity: ``horovod_reduce_op_*``, operations.cc:793-806)."""

    AVERAGE = 0
    SUM = 1
    ADASUM = 2
    MIN = 3
    MAX = 4


Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX


_LOW_PRECISION = (jnp.bfloat16, jnp.float16)


def _scale(acc, factor):
    """Multiply inside the accumulation window: ``acc`` is already at
    the accumulation dtype (fp32 for low-precision inputs), so the
    factor never rounds at 16-bit precision. No-op for factor 1 — the
    default path's program is untouched."""
    if factor != 1.0:
        acc = acc * jnp.asarray(factor, dtype=acc.dtype)
    return acc


def _scale_f32(tensor, factor):
    """Scale at fp32 regardless of input dtype (no-op for factor 1, no
    upcast then either). Scaling bf16/fp16 in their own dtype loses the
    factor's precision and can overflow for large factors — the
    prescale precision bug; every scaling site routes through here or
    ``_scale``."""
    if factor == 1.0:
        return tensor
    return tensor.astype(jnp.float32) * jnp.float32(factor)


def _apply_prescale(tensor, prescale_factor):
    """Dtype-preserving pre-scale for the per-tensor (Adasum) paths:
    fp32 math (see ``_scale_f32``), rounded back once. The elementwise
    reduce paths scale inside their fp32 accumulation window instead
    (no extra round-trip); this helper exists for callers that must
    hand a dtype-stable tensor onward (Adasum's per-tensor
    coefficients)."""
    if tensor.dtype in _LOW_PRECISION:
        return _scale_f32(tensor, prescale_factor).astype(tensor.dtype)
    return _scale(tensor, prescale_factor)


def _apply_postscale(tensor, postscale_factor):
    """Dtype-preserving post-scale; fp32 math for bf16/fp16 (see
    ``_apply_prescale``)."""
    if tensor.dtype in _LOW_PRECISION:
        return _scale_f32(tensor, postscale_factor).astype(tensor.dtype)
    return _scale(tensor, postscale_factor)


def _resolve_compression(compression):
    if compression is None:
        return None
    from ..common.compression import resolve_compression

    return resolve_compression(compression)


def allreduce(
    tensor,
    axis_name: str = AXIS_GLOBAL,
    op: int = ReduceOp.SUM,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    compression=None,
):
    """Allreduce a per-participant tensor across ``axis_name``.

    Uncompressed, low-precision inputs (bf16/fp16) are accumulated in
    fp32 — the TPU analog of the reference's AVX fp32-accumulation fp16
    path (``adasum.h:426-468``) — then cast back: the *wire* dtype is
    fp32. With ``compression`` (a ``common/compression`` compressor,
    its name, or None), floating tensors reduce IN the compressed wire
    dtype — the compiled all-reduce operand is f16/bf16, halving wire
    bytes — and post-reduction arithmetic (averaging, postscale) runs in
    fp32 on the reduced value before casting back to the input dtype.

    Pre/postscale factors are applied in fp32 inside the accumulation
    window (never in a 16-bit dtype), on both paths.

    Adasum ignores compression: its dot/norm coefficients are computed
    per tensor in fp32, and quantizing the operands would bias the
    coefficients themselves, not just the payload.
    """
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_allreduce

        return adasum_allreduce(tensor, axis_name=axis_name)

    comp = _resolve_compression(compression)
    dtype = tensor.dtype
    wire = comp.wire_dtype(dtype) if comp is not None else None
    if wire is not None:
        acc = _scale_f32(tensor, prescale_factor).astype(wire)
    else:
        acc = tensor.astype(jnp.float32) if dtype in _LOW_PRECISION else tensor
        acc = _scale(acc, prescale_factor)
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        out = lax.psum(acc, axis_name)
    elif op == ReduceOp.MIN:
        out = lax.pmin(acc, axis_name)
    elif op == ReduceOp.MAX:
        out = lax.pmax(acc, axis_name)
    else:
        raise ValueError(f"unknown reduce op {op}")
    if wire is not None:
        # fp32 accumulation on the reduced value: averaging/postscale
        # must not round at wire precision.
        out = out.astype(jnp.float32)
    if op == ReduceOp.AVERAGE:
        n = _axis_size(axis_name)
        out = out / jnp.asarray(n, dtype=out.dtype)
    return _scale(out, postscale_factor).astype(dtype)


def _grouped(tensors, reduce_fn, bucket_cap_bytes=None, compression=None):
    """The packed path: flatten, concatenate per plan bucket, reduce each
    flat buffer with ``reduce_fn``, slice the results back out.

    What is left on it is what needs a contiguous vector by construction:
    the hierarchical ladder (``grouped_hierarchical_allreduce``) scatters a
    flat buffer along the local axis. An elementwise all-reduce does not,
    and ``grouped_allreduce`` no longer comes here: on a v5e the pack was
    never "fused away". At ResNet-50's 102 MB of float32 gradients in one
    bucket the trace read, a step, 0.42 ms for the ``concatenate``, 0.14
    for the division over the whole buffer and 0.32 for the slices and
    reshapes that unpack it, beside an all-reduce of 1.79 (the 0.96 ms of
    asynchronous slices the records put with them are something else's:
    the step has them without the buffer too); the step is 1.13 ms of
    50.57 shorter by leaves (PERF.md section 6, PR 45).

    ``bucket_cap_bytes`` unset → the monolithic plan (one bucket per
    dtype, parameter order). Set → size-capped dtype-pure buckets in
    reverse parameter (≈ backward-production) order from
    ``common/fusion.plan_buckets`` (``docs/tensor-fusion.md``).
    """
    from ..common.fusion import plan_buckets_for

    if not tensors:
        return []
    flats = [jnp.ravel(t) for t in tensors]
    out = [None] * len(tensors)
    # The plan budgets the COMPRESSED wire dtype when compression is on
    # (fusion.leaf_wire_nbytes), so one HOROVOD_FUSION_THRESHOLD keeps
    # meaning wire bytes; buckets are dtype-pure either way, so the fused
    # buffer compresses as one cast inside reduce_fn.
    for bucket in plan_buckets_for(flats, bucket_cap_bytes, compression):
        idxs = list(bucket.indices)
        fused = (jnp.concatenate([flats[i] for i in idxs])
                 if len(idxs) > 1 else flats[idxs[0]])
        red = reduce_fn(fused)
        off = 0
        for i in idxs:
            n = flats[i].shape[0]
            out[i] = jnp.reshape(lax.dynamic_slice_in_dim(red, off, n),
                                 tensors[i].shape)
            off += n
    return out


def grouped_allreduce(tensors, axis_name: str = AXIS_GLOBAL, op: int = ReduceOp.SUM,
                      prescale_factor: float = 1.0, postscale_factor: float = 1.0,
                      bucket_cap_bytes=None, compression=None):
    """Allreduce a list of tensors, each leaf where it lies.

    Every leaf is ``allreduce``d by itself: nothing is ravelled,
    concatenated or sliced back out, and the scaling, the wire cast and the
    averaging are per leaf, where they fuse into the gradient's producer and
    the update's consumer. The fusion is the compiler's: XLA's all-reduce
    combiner packs the leaves' all-reduces into variadic (tuple)
    instructions that take the leaves as operands, all of them into one
    unless the enclosing ``jit`` gives it a threshold. The numbers are those
    of the packed path (``_grouped``) bit for bit: an elementwise reduction
    does not care how its elements are grouped.

    ``bucket_cap_bytes`` therefore shapes nothing that is traced here for
    sum, average, min and max: below XLA a bucket of leaves cannot be told
    from its leaves. It reaches the compiled program as the combiner's
    threshold, which ``make_train_step`` passes on a TPU when a cap is set
    (``fusion.exchange_compiler_options``).

    Adasum is NOT a per-element reduction: its dot/norm coefficients are
    per tensor, so a fused Adasum group applies the combination per
    tensor (reference ``tensor_counts`` contract,
    ``adasum_gpu_operations.cc:208-232``). There ``bucket_cap_bytes``
    (bytes, ``"auto"`` to follow ``HOROVOD_FUSION_THRESHOLD``, ``None`` for
    one group) partitions the *launch* groups; the per-tensor Adasum
    contract is unchanged.

    ``compression`` (see ``allreduce``) makes each leaf reduce in the
    compressed wire dtype. Adasum ignores it (per-tensor fp32
    coefficients).
    """
    if op == ReduceOp.ADASUM:
        from ..common.fusion import resolve_bucket_cap
        from .adasum import grouped_adasum_allreduce

        cap = resolve_bucket_cap(bucket_cap_bytes)
        pre = [_apply_prescale(t, prescale_factor) for t in tensors]
        red = _grouped_per_tensor(
            pre, lambda chunk: grouped_adasum_allreduce(
                chunk, axis_name=axis_name), cap)
        return [_apply_postscale(t, postscale_factor) for t in red]
    comp = _resolve_compression(compression)
    return [allreduce(t, axis_name=axis_name, op=op,
                      prescale_factor=prescale_factor,
                      postscale_factor=postscale_factor, compression=comp)
            for t in tensors]


def _grouped_per_tensor(tensors, group_fn, bucket_cap_bytes):
    """Bucketing for per-tensor (non-elementwise) group reductions
    (Adasum): partition the tensor list with the same backward-order
    planner, apply ``group_fn`` to each bucket's tensors as a list.
    With no cap this is a single call over the whole list — identical to
    the unbucketed path."""
    from ..common.fusion import plan_buckets_for

    if not tensors:
        return []
    if not bucket_cap_bytes:
        return group_fn(tensors)
    out = [None] * len(tensors)
    for bucket in plan_buckets_for(tensors, bucket_cap_bytes):
        idxs = list(bucket.indices)
        for i, r in zip(idxs, group_fn([tensors[i] for i in idxs])):
            out[i] = r
    return out


def hierarchical_allreduce(tensor, op: int = ReduceOp.SUM,
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0,
                           compression=None):
    """ICI-then-DCN hierarchical allreduce over the (cross, local) mesh.

    TPU-native analog of ``NCCLHierarchicalAllreduce``
    (``nccl_operations.cc:164-357``): reduce-scatter along the fast LOCAL
    (ICI) axis, allreduce the shards along the CROSS (DCN) axis, then
    all-gather back along LOCAL. Must run under the hierarchical mesh with
    axes (AXIS_CROSS, AXIS_LOCAL).

    ``compression`` runs every ladder leg (scatter, cross psum, gather)
    in the compressed wire dtype — the DCN leg is exactly where wire
    bytes hurt most — with averaging/postscale in fp32 on the reduced
    value, as in the flat path. Pre/postscale are applied in fp32 inside
    the accumulation window.
    """
    # Same dtype contract as the flat path (allreduce above): accumulate
    # low-precision inputs in fp32, cast the result back, so routing
    # through HOROVOD_HIERARCHICAL_ALLREDUCE never changes dtypes or
    # precision semantics.
    comp = _resolve_compression(compression)
    dtype = tensor.dtype
    wire = comp.wire_dtype(dtype) if comp is not None else None
    if wire is not None:
        acc = _scale_f32(tensor, prescale_factor).astype(wire)
    else:
        acc = (tensor.astype(jnp.float32)
               if dtype in _LOW_PRECISION else tensor)
        acc = _scale(acc, prescale_factor)
    flat = jnp.ravel(acc)
    local_n = _axis_size(AXIS_LOCAL)
    pad = (-flat.shape[0]) % local_n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, AXIS_LOCAL, tiled=True)
    shard = lax.psum(shard, AXIS_CROSS)
    full = lax.all_gather(shard, AXIS_LOCAL, tiled=True)
    if pad:
        full = full[: flat.shape[0] - pad]
    out = jnp.reshape(full, acc.shape)
    if wire is not None:
        out = out.astype(jnp.float32)
    if op == ReduceOp.AVERAGE:
        n = _axis_size(AXIS_LOCAL) * _axis_size(AXIS_CROSS)
        out = out / jnp.asarray(n, dtype=out.dtype)
    return _scale(out, postscale_factor).astype(dtype)


def grouped_hierarchical_allreduce(tensors, op: int = ReduceOp.SUM,
                                   prescale_factor: float = 1.0,
                                   postscale_factor: float = 1.0,
                                   bucket_cap_bytes=None, compression=None):
    """Fused hierarchical allreduce (``_grouped``'s packed buckets: the
    ladder scatters a flat vector; ICI/DCN split like
    ``hierarchical_allreduce``).
    Supports SUM/AVERAGE (``psum_scatter``-expressible) and ADASUM — the
    latter per tensor (Adasum coefficients are per-tensor; see
    ``grouped_allreduce``) via ``hierarchical_adasum_allreduce``.
    ``bucket_cap_bytes`` set or ``"auto"`` under a set
    ``HOROVOD_FUSION_THRESHOLD`` caps the buckets, unset is one per dtype;
    each bucket runs the full ICI/DCN ladder independently, so the
    scatter leg of bucket k overlaps the backward that produces bucket
    k+1."""
    from ..common.fusion import resolve_bucket_cap

    cap = resolve_bucket_cap(bucket_cap_bytes)
    if op == ReduceOp.ADASUM:
        from .adasum import grouped_hierarchical_adasum_allreduce

        pre = [_apply_prescale(t, prescale_factor) for t in tensors]
        red = _grouped_per_tensor(
            pre, grouped_hierarchical_adasum_allreduce, cap)
        return [_apply_postscale(t, postscale_factor) for t in red]
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"hierarchical allreduce supports SUM/AVERAGE/ADASUM, got op {op}")

    comp = _resolve_compression(compression)

    def reduce_fn(fused):
        # Pre/postscale ride into the ladder's accumulation window
        # (fp32/wire math there) instead of rounding at the input dtype.
        return hierarchical_allreduce(fused, op=op,
                                      prescale_factor=prescale_factor,
                                      postscale_factor=postscale_factor,
                                      compression=comp)

    return _grouped(tensors, reduce_fn, bucket_cap_bytes=cap,
                    compression=comp)


def allgather(tensor, axis_name: str = AXIS_GLOBAL):
    """Concatenate per-participant tensors along dim 0 (parity:
    ``MPIAllgather``/``NCCLAllgather`` semantics, same-shape fast path)."""
    return lax.all_gather(tensor, axis_name, tiled=True)


def hierarchical_allgather(tensor):
    """ICI-then-DCN hierarchical allgather over the (cross, local) mesh.

    TPU-native analog of ``MPIHierarchicalAllgather``
    (``mpi_operations.cc:177-328``: node-local shared-memory gather + a
    cross-node gather over node leaders): gather along the fast LOCAL (ICI)
    axis first, then exchange the per-group blocks along CROSS (DCN). With
    the global mesh laid out cross-major (rank = cross*L + local), the
    (CROSS, LOCAL) concatenation order reproduces the flat rank order."""
    local = lax.all_gather(tensor, AXIS_LOCAL, tiled=True)
    return lax.all_gather(local, AXIS_CROSS, tiled=True)


def broadcast(tensor, root_rank: int, axis_name: str = AXIS_GLOBAL):
    """Every participant receives root's tensor.

    Lowered as a masked psum, which XLA rewrites into an efficient ICI
    broadcast; avoids host-driven root designation entirely.
    """
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root_rank, tensor, jnp.zeros_like(tensor))
    # Integer/bool types are summed exactly; floats too since all-but-one
    # contribution is exactly zero.
    if tensor.dtype == jnp.bool_:
        return lax.psum(masked.astype(jnp.int32), axis_name).astype(jnp.bool_)
    return lax.psum(masked, axis_name)


def reducescatter(tensor, axis_name: str = AXIS_GLOBAL, op: int = ReduceOp.SUM):
    """Reduce-scatter along dim 0 (capability extension; the reference gained
    this op after v0.19 — included for completeness on TPU)."""
    out = lax.psum_scatter(tensor, axis_name, tiled=True)
    if op == ReduceOp.AVERAGE:
        out = out / jnp.asarray(_axis_size(axis_name), dtype=out.dtype)
    return out


def alltoall(tensor, axis_name: str = AXIS_GLOBAL):
    """Exchange equal splits of dim 0 between all participants."""
    n = _axis_size(axis_name)
    x = jnp.reshape(tensor, (n, -1) + tensor.shape[1:] if tensor.ndim > 1 else (n, tensor.shape[0] // n))
    x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)
    return jnp.reshape(x, (-1,) + tensor.shape[1:])


def barrier(axis_name: str = AXIS_GLOBAL):
    """A minimal synchronizing collective."""
    return lax.psum(jnp.ones((), dtype=jnp.int32), axis_name)


# ---- ZeRO partitioning legs (zero.py; docs/zero.md) -------------------------
#
# The named collective legs of the ZeRO step, kept here so the partition
# plane speaks the same op vocabulary as the data plane: one place owns
# the fp32-accumulation-window discipline for the scatter leg and the
# prefetch-chaining trick for the gather leg.


def zero_reducescatter(flat, axis_name: str = AXIS_GLOBAL, wire_dtype=None):
    """The gradient-partitioning leg: reduce-scatter one padded fp32
    bucket flat, each rank keeping its own 1/d shard of the sum.

    With ``wire_dtype`` (fp16/bf16 compression) the payload travels — and
    the ring accumulates — at the 16-bit wire dtype, and the reduced
    shard is upcast to fp32 before any averaging happens on it: fp32
    accumulation on the reduced value, the same window discipline as
    ``allreduce``. Callers average (``/ d``) outside, at fp32."""
    payload = flat.astype(wire_dtype) if wire_dtype is not None else flat
    seg = lax.psum_scatter(payload, axis_name, tiled=True)
    return seg.astype(jnp.float32) if wire_dtype is not None else seg


def zero_allgather(seg, axis_name: str = AXIS_GLOBAL, gather_dtype=None,
                   anchor=None):
    """The parameter-(re)assembly leg: all-gather one 1/d shard segment
    into the full padded bucket flat, optionally at a narrower
    ``gather_dtype`` (uniform-dtype models gather at the model dtype —
    half the wire bytes of fp32 for bf16 params).

    ``anchor`` is the prefetch chain (docs/zero.md): when given, the
    gather takes a dataflow dependence on it through an
    ``optimization_barrier`` — zero bytes of real data (callers pass a
    zero-length slice of an earlier gather's output), but a real edge in
    the program, so a gather chained to the gather p+1 buckets earlier
    cannot be hoisted arbitrarily far ahead of the compute front. The
    barrier bounds how many gathered bucket flats can be in flight at
    ~(p+1) without serializing consecutive gathers against compute —
    exactly the shape the latency-hiding scheduler overlaps. NOTE:
    ``optimization_barrier`` has no differentiation rule; inside a
    differentiated step this helper must be called from a
    ``custom_vjp`` forward (zero.py does), never from open AD-traced
    code."""
    if anchor is not None:
        seg, _ = lax.optimization_barrier((seg, anchor))
    if gather_dtype is not None:
        seg = seg.astype(gather_dtype)
    return lax.all_gather(seg, axis_name, tiled=True)
