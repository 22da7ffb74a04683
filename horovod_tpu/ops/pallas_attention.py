"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer stack, written for the hardware: Q/K/V tiles
stream HBM -> VMEM, the S = QK^T and P.V matmuls run on the MXU in fp32,
and the online-softmax state (running max / normalizer / accumulator)
lives in VMEM scratch across the innermost K-tile grid dimension, so the
full attention matrix never materializes (the same streaming-accumulation
math as ``parallel.ring_attention``).

Scope: forward AND backward. Training's forward emits the per-row
log-sum-exp alongside O; the backward is the standard flash backward as
two Pallas kernels — one accumulating dQ across K tiles, one accumulating
dK/dV across Q tiles — each re-materializing P = exp(S - lse) on-chip from
the saved lse, so neither pass ever writes the attention matrix to HBM.
``flash_attention_block_grads`` exposes the same per-block backward for
ring attention's backward ring pass (``parallel.ring_attention``).

Block offsets ride in as prefetched scalars, so the same kernel serves
ring attention's rotating K/V blocks (global causal masking between
sequence blocks) and the plain single-block case. On TPU the kernels
compile through Mosaic; tests interpret them on CPU
(``_resolve_dispatch``).

Each ``pallas_call`` carries a ``name`` (``flash_fwd``, ``flash_dq``,
``flash_dkv``), which jax also writes as a scope into the custom call's
``op_name``; the XLA twins carry the scope ``flash_xla``. A device trace
tells the kernels apart, and a fall-back from them, by these names
(docs/diagnostics.md, "Tracing").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common.compat import pallas_tpu_compiler_params as _compiler_params

NEG_INF = -1e30

# Tile sizes: multiples of the fp32 (8, 128) tile. 512x512 came from a
# sweep on an older stack that left no record; ROADMAP S7 re-tunes it
# against a measured roofline share. VMEM use at D=128 stays ~1 MB per
# pipeline stage.
BLOCK_Q = 512
BLOCK_K = 512


def _mxu_dot(a, b, contract):
    """``a . b`` contracting ``contract`` = ((a dims), (b dims)), float32
    accumulation. The MXU takes bf16 operands natively. float32 operands
    follow ``jax_default_matmul_precision``, as every other float32
    matmul of a model does (``_xla_flash`` included): by default they are
    rounded to bf16 on their way in, one pass (on a v5e chip 1e-2 from
    the float32 reference); under
    ``jax.default_matmul_precision("highest")`` they take the multi-pass
    float32 product (6e-7). Mosaic implements those two settings."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def row_lse(m, l):
    """Per-row log-sum-exp ``m + log(l)`` from the online-softmax state,
    for the backward's ``P = exp(S - lse)``; rows with no visible key get
    a huge POSITIVE lse so that P underflows to exactly zero for them.

    One Newton step on ``exp(y) = l`` follows the log: a v5e chip's
    float32 log is off by up to 1e-4 (its exp by 1e-5 relative), and an
    error in lse scales every P of the row — it left float32 gradients
    5e-4 from the reference where the interpreter gave 2e-5. Works on
    values inside a kernel and on arrays outside one (ring attention)."""
    safe = jnp.maximum(l, 1e-30)
    log_l = jnp.log(safe)
    log_l = log_l + (safe * jnp.exp(-log_l) - 1.0)
    return jnp.where(l > 0.0, m + log_l, -NEG_INF)


def _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, causal: bool, block_q: int, block_k: int,
                 num_k_tiles: int, return_state: bool = False,
                 mo_ref=None, lo_ref=None, lse_ref=None,
                 qs_ref=None, ks_ref=None, window=None):
    """One (batch*head, q-tile, k-tile) grid step.

    Refs: q (1, block_q, D), k/v (1, block_k, D), o (1, block_q, D);
    scratch m/l (block_q, 1) and acc (block_q, D) carry the online-softmax
    state across the sequential k dimension. offs = [q_off, k_off] global
    token offsets of sequence block 0 (ring attention rotates k blocks).
    qs/ks (1, block, 1) int32: optional packed-sequence segment ids —
    the mask composes with causal at trace time, so the segment-free
    path compiles identically to before.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # program_id must be read OUTSIDE pl.when bodies (the predicated
    # sub-jaxpr escapes the interpreter's program_id rewrite).
    qi = pl.program_id(1)
    q_base = offs_ref[0] + qi * block_q
    k_base = offs_ref[1] + ki * block_k
    if causal:
        # Causal tile culling: a K tile strictly in this Q tile's future
        # contributes nothing — predicate the whole update away (halves
        # the causal FLOPs; the reference flash kernels do the same).
        visible = q_base + block_q - 1 >= k_base
        if window is not None:
            # Sliding-window culling: a K tile entirely beyond the
            # window into this Q tile's past is dead too — for
            # T >> window most tiles skip, the real SWA saving.
            visible = jnp.logical_and(
                visible, k_base + block_k - 1 >= q_base - (window - 1))
    else:
        visible = True

    @pl.when(visible)
    def _update():
        # Feed the MXU its native input dtype (bf16 x bf16 -> f32
        # accumulate); pre-casting to f32 would halve matmul throughput.
        q = q_ref[0]
        k = k_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = _mxu_dot(q, k, ((1,), (1,))) * scale  # [bq, bk]

        if causal:
            q_pos = (q_base +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            k_pos = (k_base +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
        if qs_ref is not None:
            s = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, -1),
                          s, NEG_INF)

        m_prev = m_ref[:]                      # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alive = m_new > NEG_INF / 2
        corr = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # P rides the MXU in the V dtype (f32 accumulation preserved by
        # preferred_element_type) — the standard TPU flash-kernel trade.
        pv = _mxu_dot(p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(ki == num_k_tiles - 1)
    def _finalize():
        if return_state:
            # Block mode (ring attention): emit the UNnormalized
            # accumulator plus (m, l) so the caller merges blocks with the
            # standard online-softmax combine.
            o_ref[0] = acc_ref[:].astype(o_ref.dtype)
            mo_ref[0] = m_ref[:]
            lo_ref[0] = l_ref[:]
        else:
            o_ref[0] = (acc_ref[:] /
                        jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0] = row_lse(m_ref[:], l_ref[:])


def _attn_kernel_state(offs_ref, q_ref, k_ref, v_ref, o_ref, mo_ref,
                       lo_ref, m_ref, l_ref, acc_ref, **kw):
    """Block-mode positional adapter: pallas passes outputs before
    scratch, so the three outputs (acc, m, l) precede the scratch refs."""
    _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, return_state=True, mo_ref=mo_ref, lo_ref=lo_ref,
                 **kw)


def _attn_kernel_state_seg(offs_ref, q_ref, k_ref, v_ref, qs_ref, ks_ref,
                           o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref,
                           **kw):
    """Block-mode adapter with segment-id tiles (inputs ride after v)."""
    _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, return_state=True, mo_ref=mo_ref, lo_ref=lo_ref,
                 qs_ref=qs_ref, ks_ref=ks_ref, **kw)


def _attn_kernel_train(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_ref, l_ref, acc_ref, **kw):
    """Training-forward adapter: normalized O plus the per-row lse
    residual the flash backward re-materializes P from."""
    _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, lse_ref=lse_ref, **kw)


def _attn_kernel_train_seg(offs_ref, q_ref, k_ref, v_ref, qs_ref, ks_ref,
                           o_ref, lse_ref, m_ref, l_ref, acc_ref, **kw):
    """Training-forward adapter with segment-id tiles."""
    _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, lse_ref=lse_ref, qs_ref=qs_ref, ks_ref=ks_ref,
                 **kw)


def _attn_bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dq_acc, *, causal: bool,
                        block_q: int, block_k: int, num_k_tiles: int,
                        qs_ref=None, ks_ref=None, window=None):
    """dQ pass: grid (batch*head, q-tile, k-tile), sequential over K tiles.

    P = exp(S - lse) is rebuilt on-chip from the saved lse;
    dS = P * (dO.V^T - delta); dQ accumulates dS.K in VMEM across the K
    dimension. delta = rowsum(dO * O), precomputed by the caller.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    qi = pl.program_id(1)
    q_base = offs_ref[0] + qi * block_q
    k_base = offs_ref[1] + ki * block_k
    visible = (q_base + block_q - 1 >= k_base) if causal else True
    if causal and window is not None:
        visible = jnp.logical_and(
            visible, k_base + block_k - 1 >= q_base - (window - 1))

    @pl.when(visible)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = _mxu_dot(q, k, ((1,), (1,))) * scale  # [bq, bk]
        p = jnp.exp(s - lse_ref[0])                          # [bq, bk]
        if causal:
            q_pos = q_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
            if window is not None:
                p = jnp.where(q_pos - k_pos < window, p, 0.0)
        if qs_ref is not None:
            p = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, -1), p, 0.0)
        dp = _mxu_dot(do_ref[0], v_ref[0], ((1,), (1,)))  # [bq, bk]
        ds = p * (dp - delta_ref[0]) * scale  # [bq, bk]
        dq_acc[:] += _mxu_dot(ds.astype(k.dtype), k, ((1,), (0,)))  # [bq, D]

    @pl.when(ki == num_k_tiles - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _attn_bwd_dq_kernel_seg(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, qs_ref, ks_ref, dq_ref, dq_acc,
                            **kw):
    """dQ adapter with segment-id tiles (inputs ride after delta)."""
    _attn_bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dq_acc, qs_ref=qs_ref,
                        ks_ref=ks_ref, **kw)


def _attn_bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                         causal: bool, block_q: int, block_k: int,
                         num_q_tiles: int, qs_ref=None, ks_ref=None,
                         window=None):
    """dK/dV pass: grid (batch*head, k-tile, q-tile), sequential over Q
    tiles. Same [bq, bk] orientation as the dQ pass; the transposed
    contractions (P^T.dO, dS^T.Q) ride dot_general dimension numbers so
    no tile is ever explicitly transposed."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    ki = pl.program_id(1)
    q_base = offs_ref[0] + qi * block_q
    k_base = offs_ref[1] + ki * block_k
    visible = (q_base + block_q - 1 >= k_base) if causal else True
    if causal and window is not None:
        visible = jnp.logical_and(
            visible, k_base + block_k - 1 >= q_base - (window - 1))

    @pl.when(visible)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = _mxu_dot(q, k, ((1,), (1,))) * scale  # [bq, bk]
        p = jnp.exp(s - lse_ref[0])
        if causal:
            q_pos = q_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
            if window is not None:
                p = jnp.where(q_pos - k_pos < window, p, 0.0)
        if qs_ref is not None:
            p = jnp.where(qs_ref[0] == ks_ref[0].reshape(1, -1), p, 0.0)
        dv_acc[:] += _mxu_dot(p.astype(do.dtype), do, ((0,), (0,)))  # [bk, D]
        dp = _mxu_dot(do, v_ref[0], ((1,), (1,)))  # [bq, bk]
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += _mxu_dot(ds.astype(q.dtype), q, ((0,), (0,)))  # [bk, D]

    @pl.when(qi == num_q_tiles - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _attn_bwd_dkv_kernel_seg(offs_ref, q_ref, k_ref, v_ref, do_ref,
                             lse_ref, delta_ref, qs_ref, ks_ref, dk_ref,
                             dv_ref, dk_acc, dv_acc, **kw):
    """dK/dV adapter with segment-id tiles (inputs ride after delta)."""
    _attn_bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         qs_ref=qs_ref, ks_ref=ks_ref, **kw)


def _seg3(seg):
    """[BH, T] int32 -> [BH, T, 1]: the row-oriented layout the lse/delta
    tiles already use. Mosaic requires the last two block dims be
    (8, 128)-divisible or full-extent; a (1, block, 1) tile satisfies
    that for EVERY _pick_block size (block >= 8 on the sublane dim, the
    lane dim full at 1) — the lane-major (1, 1, block) layout fails for
    blocks < 128."""
    return seg[:, :, None]


def _seg_specs(bq, bk):
    """BlockSpecs for the (1, block, 1) int32 segment-id tiles."""
    return [
        pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, offs: (bh, qi, 0)),
        pl.BlockSpec((1, bk, 1), lambda bh, qi, ki, offs: (bh, ki, 0)),
    ]


def int_cotangent(x):
    """Symbolic-zero cotangent for an optional integer array argument of
    a custom_vjp (None passes through)."""
    import numpy as np

    return None if x is None else np.zeros(x.shape,
                                           dtype=jax.dtypes.float0)


def _pallas_block_state(q, k, v, offs, causal: bool, interpret: bool,
                        q_seg=None, k_seg=None, window=None):
    """q/k/v: [BH, T, D]. Returns (acc f32 [BH,Tq,D], m f32 [BH,Tq,1],
    l f32 [BH,Tq,1]) — the unmerged online-softmax state of this K block
    (ring attention merges blocks as they rotate). ``q_seg``/``k_seg``:
    optional int32 [BH, T] segment ids (streamed as extra tiles)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq, BLOCK_Q)
    bk = _pick_block(Tk, BLOCK_K)
    num_q = Tq // bq
    num_k = Tk // bk

    from jax.experimental.pallas import tpu as pltpu

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki, offs: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
    ]
    args = [offs, q, k, v]
    if q_seg is not None:
        in_specs += _seg_specs(bq, bk)
        args += [_seg3(q_seg), _seg3(k_seg)]
        kernel_fn = _attn_kernel_state_seg
    else:
        kernel_fn = _attn_kernel_state
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, num_q, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D),
                         lambda bh, qi, ki, offs: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda bh, qi, ki, offs: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda bh, qi, ki, offs: (bh, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        kernel_fn, causal=causal, block_q=bq, block_k=bk,
        num_k_tiles=num_k, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _apply_segment_mask(x, q_seg, k_seg, fill):
    """Packed-sequence masking, the single definition: positions with
    differing segment ids take ``fill`` (NEG_INF on scores, 0 on
    probabilities). x: [BH, Tq, Tk]; q_seg/k_seg: int32 [BH, T]."""
    return jnp.where(q_seg[:, :, None] == k_seg[:, None, :], x, fill)


def _require_both_segs(q_seg, k_seg):
    if (q_seg is None) != (k_seg is None):
        raise ValueError("pass both q_segment_ids and k_segment_ids")


def _check_window(window, causal):
    if window is None:
        return
    if not causal:
        raise ValueError("sliding-window attention is defined for the "
                         "causal case; pass causal=True with window")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


@jax.named_scope("flash_xla")
def _xla_block_state(q, k, v, offs, causal, q_seg=None, k_seg=None,
                     window=None):
    """XLA twin of the block-mode kernel (backward recompute + fallback).
    ``offs`` = int32[2] (q_off, k_off) — an array, not statics, because
    ring attention traces the rotating block origin. ``q_seg``/``k_seg``:
    optional int32 [BH, T] per-block segment ids (packed sequences)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + offs[0]
        ik = jnp.arange(k.shape[1])[None, :] + offs[1]
        s = jnp.where(iq >= ik, s, NEG_INF)
        if window is not None:
            s = jnp.where(iq - ik < window, s, NEG_INF)
    if q_seg is not None:
        s = _apply_segment_mask(s, q_seg, k_seg, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bts,bsd->btd", p.astype(v.dtype),
                     v).astype(jnp.float32)
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _block_state_core(q, k, v, offs, q_seg, k_seg, causal, interpret,
                      window):
    if _pick_block(q.shape[1], BLOCK_Q) is None or \
            _pick_block(k.shape[1], BLOCK_K) is None:
        return _xla_block_state(q, k, v, offs, causal, q_seg=q_seg,
                                k_seg=k_seg, window=window)
    return _pallas_block_state(q, k, v, offs, causal, interpret,
                               q_seg=q_seg, k_seg=k_seg, window=window)


def _block_state_fwd(q, k, v, offs, q_seg, k_seg, causal, interpret,
                     window):
    return _block_state_core(q, k, v, offs, q_seg, k_seg, causal,
                             interpret, window), (q, k, v, offs, q_seg,
                                                  k_seg)


def _block_state_bwd(causal, interpret, window, res, g):
    import numpy as np

    q, k, v, offs, q_seg, k_seg = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _xla_block_state(q_, k_, v_, offs, causal,
                                            q_seg=q_seg, k_seg=k_seg,
                                            window=window),
        q, k, v)
    dq, dk, dv = vjp(g)

    # Integer offsets/segment ids carry the symbolic-zero cotangent.
    return (dq, dk, dv, np.zeros((2,), dtype=jax.dtypes.float0),
            int_cotangent(q_seg), int_cotangent(k_seg))


_block_state_core.defvjp(_block_state_fwd, _block_state_bwd)


def _resolve_dispatch(use_pallas: Optional[bool]):
    """Shared backend policy: (use_pallas, interpret). Mosaic on TPU.
    Off the chip the kernels run only interpreted, and only where a test
    asked for that with HVD_PALLAS_INTERPRET=1: ``None`` otherwise takes
    the XLA path and an explicit ``True`` raises — a kernel never runs
    interpreted without anyone asking."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu and bool(os.environ.get("HVD_PALLAS_INTERPRET"))
    if use_pallas is None:
        use_pallas = on_tpu or interpret
    elif use_pallas and not (on_tpu or interpret):
        raise RuntimeError(
            f"use_pallas=True on the {jax.default_backend()!r} backend: "
            "the Mosaic kernels compile for TPU only (tests interpret "
            "them under HVD_PALLAS_INTERPRET=1)")
    return use_pallas, use_pallas and interpret


def _merge_heads(x):
    """[B, T, H, D] -> [B*H, T, D]."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def flash_attention_block(q, k, v, q_off, k_off, causal: bool = True,
                          use_pallas: Optional[bool] = None,
                          q_segment_ids=None, k_segment_ids=None,
                          window: Optional[int] = None):
    """One K/V block's unmerged attention state for ring attention.

    q/k/v: [B, T, H, D]. Returns (acc, m, l) with acc f32 [B, T, H, D]
    (unnormalized P.V), m/l f32 [B, H, T] — merge across blocks with the
    online-softmax combine. Dispatch rules match ``flash_attention``
    (shared ``_resolve_dispatch``); segment ids stream into the same
    kernels as extra id tiles (packed sequences).
    """
    B, Tq, H, D = q.shape

    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    _require_both_segs(q_segment_ids, k_segment_ids)
    q_seg = k_seg = None
    if q_segment_ids is not None:
        q_seg = _tile_seg(q_segment_ids, H)
        k_seg = _tile_seg(k_segment_ids, H)
    _check_window(window, causal)
    use_pallas, interpret = _resolve_dispatch(use_pallas)
    if use_pallas:
        acc, m, l = _block_state_core(
            _merge_heads(q), _merge_heads(k), _merge_heads(v), offs,
            q_seg, k_seg, causal, interpret, window)
    else:
        acc, m, l = _xla_block_state(
            _merge_heads(q), _merge_heads(k), _merge_heads(v), offs,
            causal, q_seg=q_seg, k_seg=k_seg, window=window)
    acc = acc.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    m = m.reshape(B, H, Tq)
    l = l.reshape(B, H, Tq)
    return acc, m, l


def flash_attention_block_grads(q, k, v, do, lse, delta, q_off, k_off,
                                causal: bool = True,
                                use_pallas: Optional[bool] = None,
                                q_segment_ids=None, k_segment_ids=None,
                                window: Optional[int] = None):
    """One K/V block's (dq, dk, dv) for ring attention's backward pass.

    q/k/v/do: [B, T, H, D]; lse/delta: f32 [B, H, T] — the GLOBAL row
    statistics (lse over all keys, delta = rowsum(dO*O)), so each block's
    P = exp(S - lse) is already globally normalized and the per-block
    gradients simply sum across the ring. Returns f32 arrays in the
    [B, T, H, D] layout (f32 so the ring's cross-block accumulation
    doesn't round at the model dtype each step).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    use_pallas, interpret = _resolve_dispatch(use_pallas)

    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    qm, km, vm, dom = (_merge_heads(x) for x in (q, k, v, do))
    lse_m = lse.reshape(B * H, Tq, 1)
    delta_m = delta.reshape(B * H, Tq, 1)
    _require_both_segs(q_segment_ids, k_segment_ids)
    q_seg = k_seg = None
    if q_segment_ids is not None:
        q_seg = _tile_seg(q_segment_ids, H)
        k_seg = _tile_seg(k_segment_ids, H)
    _check_window(window, causal)
    if use_pallas and _pick_block(Tq, BLOCK_Q) is not None and \
            _pick_block(Tk, BLOCK_K) is not None:
        dq, dk, dv = _pallas_bwd(qm, km, vm, dom, lse_m, delta_m, offs,
                                 causal, interpret, out_dtype=jnp.float32,
                                 q_seg=q_seg, k_seg=k_seg, window=window)
    else:
        dq, dk, dv = _xla_block_grads(qm, km, vm, dom, lse_m, delta_m,
                                      offs, causal, out_dtype=jnp.float32,
                                      q_seg=q_seg, k_seg=k_seg,
                                      window=window)

    def split(x, t):
        return x.reshape(B, H, t, D).transpose(0, 2, 1, 3)

    return split(dq, Tq), split(dk, Tk), split(dv, Tk)


def _attn_kernel_seg(offs_ref, q_ref, k_ref, v_ref, qs_ref, ks_ref,
                     o_ref, m_ref, l_ref, acc_ref, **kw):
    """Plain-forward adapter with segment-id tiles (no lse residual)."""
    _attn_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, qs_ref=qs_ref, ks_ref=ks_ref, **kw)


def _pallas_attention_fwd(q, k, v, q_off, k_off, causal: bool,
                          interpret: bool, q_seg=None, k_seg=None,
                          window=None):
    """q/k/v: [BH, T, D] (already merged batch*heads, padded to tiles)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq, BLOCK_Q)
    bk = _pick_block(Tk, BLOCK_K)
    num_q = Tq // bq
    num_k = Tk // bk

    from jax.experimental.pallas import tpu as pltpu

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki, offs: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
    ]
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    args = [offs, q, k, v]
    if q_seg is not None:
        in_specs += _seg_specs(bq, bk)
        args += [_seg3(q_seg), _seg3(k_seg)]
        kernel_fn = _attn_kernel_seg
    else:
        kernel_fn = _attn_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, num_q, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D),
                               lambda bh, qi, ki, offs: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        kernel_fn, causal=causal, block_q=bq, block_k=bk,
        num_k_tiles=num_k, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _pallas_attention_fwd_train(q, k, v, offs, causal: bool,
                                interpret: bool, q_seg=None, k_seg=None,
                                window=None):
    """Forward with residuals: (o [BH,T,D] in q.dtype, lse f32 [BH,T,1])."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq, BLOCK_Q)
    bk = _pick_block(Tk, BLOCK_K)
    num_q = Tq // bq
    num_k = Tk // bk

    from jax.experimental.pallas import tpu as pltpu

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, qi, ki, offs: (bh, qi, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
        pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0)),
    ]
    args = [offs, q, k, v]
    if q_seg is not None:
        in_specs += _seg_specs(bq, bk)
        args += [_seg3(q_seg), _seg3(k_seg)]
        kernel_fn = _attn_kernel_train_seg
    else:
        kernel_fn = _attn_kernel_train
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, num_q, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki, offs: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, offs: (bh, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        kernel_fn, causal=causal, block_q=bq, block_k=bk,
        num_k_tiles=num_k, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _pallas_bwd(q, k, v, do, lse, delta, offs, causal: bool,
                interpret: bool, out_dtype=None, q_seg=None, k_seg=None,
                window=None):
    """The two flash-backward kernels; returns (dq, dk, dv) in the input
    dtypes (or ``out_dtype`` when given — ring accumulation wants f32).
    lse/delta: f32 [BH, T, 1]."""
    dq_dt = out_dtype or q.dtype
    dk_dt = out_dtype or k.dtype
    dv_dt = out_dtype or v.dtype
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = _pick_block(Tq, BLOCK_Q)
    bk = _pick_block(Tk, BLOCK_K)
    num_q = Tq // bq
    num_k = Tk // bk

    from jax.experimental.pallas import tpu as pltpu

    q_spec = pl.BlockSpec((1, bq, D), lambda bh, qi, ki, offs: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, bk, D), lambda bh, qi, ki, offs: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda bh, qi, ki, offs: (bh, qi, 0))
    dq_in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    dq_args = [offs, q, k, v, do, lse, delta]
    if q_seg is not None:
        dq_in_specs += _seg_specs(bq, bk)
        dq_args += [_seg3(q_seg), _seg3(k_seg)]
        dq_kernel = _attn_bwd_dq_kernel_seg
    else:
        dq_kernel = _attn_bwd_dq_kernel
    dq = pl.pallas_call(
        functools.partial(dq_kernel, causal=causal, block_q=bq,
                          block_k=bk, num_k_tiles=num_k, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, num_q, num_k),
            in_specs=dq_in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), dq_dt),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(*dq_args)

    # dK/dV pass: K tiles are the parallel dimension, Q tiles sequential.
    qkv_spec = pl.BlockSpec((1, bq, D), lambda bh, ki, qi, offs: (bh, qi, 0))
    kkv_spec = pl.BlockSpec((1, bk, D), lambda bh, ki, qi, offs: (bh, ki, 0))
    rowkv_spec = pl.BlockSpec((1, bq, 1),
                              lambda bh, ki, qi, offs: (bh, qi, 0))
    kv_in_specs = [qkv_spec, kkv_spec, kkv_spec, qkv_spec, rowkv_spec,
                   rowkv_spec]
    kv_args = [offs, q, k, v, do, lse, delta]
    if q_seg is not None:
        kv_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi, offs: (bh, qi, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, ki, qi, offs: (bh, ki, 0)),
        ]
        kv_args += [_seg3(q_seg), _seg3(k_seg)]
        kv_kernel = _attn_bwd_dkv_kernel_seg
    else:
        kv_kernel = _attn_bwd_dkv_kernel
    dk, dv = pl.pallas_call(
        functools.partial(kv_kernel, causal=causal, block_q=bq,
                          block_k=bk, num_q_tiles=num_q, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, num_k, num_q),
            in_specs=kv_in_specs,
            out_specs=[kkv_spec, kkv_spec],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, D), dk_dt),
                   jax.ShapeDtypeStruct((BH, Tk, D), dv_dt)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(*kv_args)
    return dq, dk, dv


@jax.named_scope("flash_xla")
def _xla_block_grads(q, k, v, do, lse, delta, offs, causal: bool,
                     out_dtype=None, q_seg=None, k_seg=None, window=None):
    """XLA twin of the backward kernels (fallback for untileable shapes
    and non-TPU platforms). Same math, same lse/delta residuals."""
    dq_dt = out_dtype or q.dtype
    dk_dt = out_dtype or k.dtype
    dv_dt = out_dtype or v.dtype
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jnp.exp(s - lse)
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + offs[0]
        ik = jnp.arange(k.shape[1])[None, :] + offs[1]
        p = jnp.where((iq >= ik)[None], p, 0.0)
        if window is not None:
            p = jnp.where((iq - ik < window)[None], p, 0.0)
    if q_seg is not None:
        p = _apply_segment_mask(p, q_seg, k_seg, 0.0)
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bts,btd->bsd", p, dof)
    dp = jnp.einsum("btd,bsd->bts", dof, v.astype(jnp.float32))
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bts,bsd->btd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bts,btd->bsd", ds, q.astype(jnp.float32))
    return dq.astype(dq_dt), dk.astype(dk_dt), dv.astype(dv_dt)


def _pick_block(t: int, cap: int) -> Optional[int]:
    """Largest MXU-friendly tile (multiple of the fp32 sublane count, up
    to ``cap``) that divides ``t``; None when ``t`` isn't tileable
    (callers fall back to the XLA path rather than reason about
    padded-position masking). Candidates extend above the 512 default so
    a BLOCK_Q/BLOCK_K override (tools/pallas_bench.py --sweep-blocks)
    genuinely changes the tiling."""
    for c in (2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if c <= cap and t % c == 0:
            return c
    return None


@jax.named_scope("flash_xla")
def _xla_flash(q, k, v, q_off, k_off, causal, q_seg=None, k_seg=None,
               window=None):
    """XLA reference path (backward recompute + non-TPU fallback), fp32
    accumulation — the same math as parallel.ring_attention.
    ``q_seg``/``k_seg``: optional int32 [BH, T] segment ids (packed
    sequences); tokens attend only within their segment."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + q_off
        ik = jnp.arange(k.shape[1])[None, :] + k_off
        s = jnp.where(iq >= ik, s, NEG_INF)
        if window is not None:
            s = jnp.where(iq - ik < window, s, NEG_INF)
    if q_seg is not None:
        s = _apply_segment_mask(s, q_seg, k_seg, NEG_INF)
    # Rows whose keys are all masked normalize to zero output, matching
    # the kernel's max(l, eps) guard.
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m))
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bts,bsd->btd", p / l, v.astype(jnp.float32))
    return o.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_core(q, k, v, q_seg, k_seg, q_off, k_off, causal, interpret,
                window):
    if _pick_block(q.shape[1], BLOCK_Q) is None or \
            _pick_block(k.shape[1], BLOCK_K) is None:
        return _xla_flash(q, k, v, q_off, k_off, causal, q_seg=q_seg,
                          k_seg=k_seg, window=window)
    return _pallas_attention_fwd(q, k, v, q_off, k_off, causal, interpret,
                                 q_seg=q_seg, k_seg=k_seg, window=window)


def _flash_fwd(q, k, v, q_seg, k_seg, q_off, k_off, causal, interpret,
               window):
    if _pick_block(q.shape[1], BLOCK_Q) is None or \
            _pick_block(k.shape[1], BLOCK_K) is None:
        return _xla_flash(q, k, v, q_off, k_off, causal, q_seg=q_seg,
                          k_seg=k_seg, window=window), \
            (q, k, v, q_seg, k_seg, None, None)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    o, lse = _pallas_attention_fwd_train(q, k, v, offs, causal, interpret,
                                         q_seg=q_seg, k_seg=k_seg,
                                         window=window)
    return o, (q, k, v, q_seg, k_seg, o, lse)


def _flash_bwd(q_off, k_off, causal, interpret, window, res, g):
    q, k, v, q_seg, k_seg, o, lse = res

    if lse is None:
        # Untileable shapes: recompute through the XLA twin.
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _xla_flash(q_, k_, v_, q_off, k_off, causal,
                                          q_seg=q_seg, k_seg=k_seg,
                                          window=window),
            q, k, v)
        return (*vjp(g), int_cotangent(q_seg), int_cotangent(k_seg))
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    dq, dk, dv = _pallas_bwd(q, k, v, g, lse, delta, offs, causal,
                             interpret, q_seg=q_seg, k_seg=k_seg,
                             window=window)
    return dq, dk, dv, int_cotangent(q_seg), int_cotangent(k_seg)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _tile_seg(seg, heads):
    """[B, T] int segment ids -> [B*H, T] aligned with _merge_heads."""
    return jnp.repeat(jnp.asarray(seg, jnp.int32), heads, axis=0)


def flash_attention(q, k, v, causal: bool = True, q_off: int = 0,
                    k_off: int = 0, use_pallas: Optional[bool] = None,
                    q_segment_ids=None, k_segment_ids=None,
                    window: Optional[int] = None):
    """Blocked flash attention. q/k/v: [B, T, H, D].

    ``use_pallas=None`` auto-selects via ``_resolve_dispatch``.
    ``q_off``/``k_off`` are the global token offsets of the blocks — ring
    attention passes the rotating K block's origin so causal masking stays
    globally correct.

    ``q_segment_ids``/``k_segment_ids`` (int [B, T]): packed-sequence
    masking — a token attends only to keys with its segment id (composed
    with the causal mask). The Mosaic kernels stream the ids as extra
    (1, block) int32 tiles; the mask composes at trace time so the
    segment-free path compiles unchanged.
    """
    B, Tq, H, D = q.shape

    def split(x, t):
        return x.reshape(B, H, t, D).transpose(0, 2, 1, 3)

    _require_both_segs(q_segment_ids, k_segment_ids)
    _check_window(window, causal)
    q_seg = k_seg = None
    if q_segment_ids is not None:
        q_seg = _tile_seg(q_segment_ids, H)
        k_seg = _tile_seg(k_segment_ids, H)

    use_pallas, interpret = _resolve_dispatch(use_pallas)
    if not use_pallas:
        out = _xla_flash(_merge_heads(q), _merge_heads(k), _merge_heads(v),
                         q_off, k_off, causal, q_seg=q_seg, k_seg=k_seg,
                         window=window)
        return split(out, Tq)
    out = _flash_core(_merge_heads(q), _merge_heads(k), _merge_heads(v),
                      q_seg, k_seg, q_off, k_off, causal, interpret,
                      window)
    return split(out, Tq)
