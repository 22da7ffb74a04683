"""Ring attention: context parallelism for long sequences over ICI.

First-class long-context support (absent from the reference, SURVEY §5
"Long-context / sequence parallelism"): the sequence is sharded across the
``sp`` mesh axis; each chip holds its Q block while K/V blocks rotate around
the ring via ``lax.ppermute``, with online-softmax (flash-style) accumulation
so the full attention matrix never materializes. Communication of the next
K/V block overlaps with compute of the current one under XLA's async
collective-permute scheduling on ICI.

Numerics: log-sum-exp streaming accumulation in float32 regardless of input
dtype — the same max-shifted accumulation flash attention uses.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..common.compat import axis_size as _axis_size

NEG_INF = -1e30


def _block_attend(q, k, v, m, l, o, mask):
    """One flash-attention block update.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; m/l: [B, H, Tq]; o: [B, Tq, H, D]
    mask: [Tq, Tk] additive (0 or NEG_INF), or None.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = s + mask[None, None, :, :]
    m_blk = jnp.max(s, axis=-1)  # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    # Guard fully-masked blocks: exp(NEG_INF - NEG_INF) must not be 1.
    alive = m_new > NEG_INF / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 1.0)
    # Masked entries have s == NEG_INF; when a whole tile is masked
    # m_new == NEG_INF too and exp(s - m_new) would be exp(0) = 1, so zero
    # them explicitly instead of relying on underflow.
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_fwd_pass(q, k, v, seg, axis_name: str, causal: bool,
                   window=None):
    """The forward ring: flash block kernel per rotating K/V block +
    online-softmax merge. Returns (o in q.dtype, lse f32 [B, H, Tq]) —
    lse is the backward pass's residual. ``seg``: optional int32 [B, T]
    local segment ids (packed sequences); the K-side ids rotate with
    their K/V block."""
    sp = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    m = jnp.full((B, H, Tq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((B, H, Tq), dtype=jnp.float32)
    o = jnp.zeros((B, Tq, H, D), dtype=jnp.float32)

    fwd_perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(carry, step):
        m, l, o, k_cur, v_cur, kseg_cur = carry
        # k_cur originated at rank (my - step) mod sp. Each block's local
        # attention state comes from the flash kernel (Pallas on TPU, XLA
        # elsewhere); the cross-block merge below is the standard
        # online-softmax combine. GQA K/V travel the ring at their own
        # head count, and the kernels read them at it (query head j reads
        # K/V head j // g through the index map).
        from ..ops.pallas_attention import (
            flash_attention_block, merge_state)

        k_blk = (my - step) % sp
        acc_b, m_b, l_b = flash_attention_block(
            q, k_cur, v_cur, q_off=my * Tq,
            k_off=k_blk * k_cur.shape[1],
            causal=causal, q_segment_ids=seg,
            k_segment_ids=None if seg is None else kseg_cur,
            window=window)
        m_new, l, o = merge_state(m, l, o, m_b, l_b, acc_b)  # m [B,H,Tq]
        k_nxt = lax.ppermute(k_cur, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_cur, axis_name, fwd_perm)
        kseg_nxt = (kseg_cur if seg is None else
                    lax.ppermute(kseg_cur, axis_name, fwd_perm))
        return (m_new, l, o, k_nxt, v_nxt, kseg_nxt), None

    kseg0 = jnp.zeros((B, Tq), jnp.int32) if seg is None else seg
    (m, l, o, _, _, _), _ = lax.scan(
        body, (m, l, o, k, v, kseg0), jnp.arange(sp))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    from ..ops.pallas_attention import row_lse

    lse = row_lse(m, l)
    # Anchor the axis index in the live output dataflow: when the mask
    # path doesn't consume it (causal=False, no window/segments), some
    # XLA versions leave the dead partition-id where the SPMD partitioner
    # rejects it ("PartitionId instruction is not supported for SPMD
    # partitioning", jaxlib 0.4.x CPU). A zero-weight use costs nothing
    # and keeps the op inside the manual region.
    o = o + (my * 0).astype(o.dtype)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_core(q, k, v, seg, axis_name, causal, window):
    return _ring_fwd_pass(q, k, v, seg, axis_name, causal, window)[0]


def _ring_vjp_fwd(q, k, v, seg, axis_name, causal, window):
    o, lse = _ring_fwd_pass(q, k, v, seg, axis_name, causal, window)
    return o, (q, k, v, seg, o, lse)


def _ring_vjp_bwd(axis_name, causal, window, res, do):
    """Backward ring pass (the ring-attention paper's second rotation):
    K/V blocks rotate again, each visit computes that block's (dq, dk, dv)
    through the flash backward kernels with the GLOBAL lse/delta
    residuals, and dK/dV accumulators travel with their blocks — after sp
    rotations every gradient is home. Twice the forward's ppermute bytes
    (k, v, dk, dv per step), the standard ring-backward cost. With GQA the
    kernels return a block's dK/dV already summed over each group, so the
    accumulators rotate at the K/V head count."""
    from ..ops.pallas_attention import flash_attention_block_grads

    q, k, v, seg, o, lse = res
    sp = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)            # [B, H, Tq]

    fwd_perm = [(i, (i + 1) % sp) for i in range(sp)]
    dq0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    dk0 = jnp.zeros((B, Tk, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, Tk, Hkv, D), jnp.float32)

    def body(carry, step):
        dq, dk, dv, k_cur, v_cur, kseg_cur = carry
        k_blk = (my - step) % sp
        dq_b, dk_b, dv_b = flash_attention_block_grads(
            q, k_cur, v_cur, do, lse, delta,
            q_off=my * Tq, k_off=k_blk * Tk, causal=causal,
            q_segment_ids=seg,
            k_segment_ids=None if seg is None else kseg_cur,
            window=window)
        dq = dq + dq_b
        dk = dk + dk_b
        dv = dv + dv_b
        k_nxt = lax.ppermute(k_cur, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_cur, axis_name, fwd_perm)
        kseg_nxt = (kseg_cur if seg is None else
                    lax.ppermute(kseg_cur, axis_name, fwd_perm))
        dk = lax.ppermute(dk, axis_name, fwd_perm)
        dv = lax.ppermute(dv, axis_name, fwd_perm)
        return (dq, dk, dv, k_nxt, v_nxt, kseg_nxt), None

    kseg0 = jnp.zeros((B, Tq), jnp.int32) if seg is None else seg
    (dq, dk, dv, _, _, _), _ = lax.scan(
        body, (dq0, dk0, dv0, k, v, kseg0), jnp.arange(sp))
    from ..ops.pallas_attention import int_cotangent

    # Same partition-id anchor as the forward pass (see _ring_fwd_pass).
    dq = dq + (my * 0).astype(dq.dtype)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            int_cotangent(seg))


_ring_core.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   segment_ids=None, window=None):
    """Context-parallel attention. q: [B, T_local, H, D] per chip, k/v:
    [B, T_local, Hkv, D] with Hkv a divisor of H (grouped-query
    attention: the kernels read K/V at Hkv heads).

    Every K/V block's local attention runs through the flash kernel
    (Pallas/Mosaic on TPU, XLA elsewhere — ``ops.pallas_attention``):
    sp == 1 is a single full-attention kernel call; sp > 1 calls the
    block-state kernel once per ring step and merges blocks with the
    online-softmax combine, while ``ppermute`` rotates K/V so transfer
    overlaps compute under XLA's collective scheduling. Training's
    backward is a second ring pass through the flash backward kernels
    (``_ring_vjp_bwd``) — no attention recompute through XLA.

    ``segment_ids`` (int [B, T_local], sequence-sharded like q):
    packed-sequence masking — tokens attend only within their segment;
    the K-side ids rotate around the ring with their K/V block and
    stream into the flash kernels as extra id tiles.
    """
    sp = _axis_size(axis_name)
    if sp == 1:
        from ..ops.pallas_attention import flash_attention

        return flash_attention(q, k, v, causal=causal,
                               q_segment_ids=segment_ids,
                               k_segment_ids=segment_ids, window=window)
    if segment_ids is not None:
        segment_ids = jnp.asarray(segment_ids, jnp.int32)
    return _ring_core(q, k, v, segment_ids, axis_name, causal, window)


def local_flash_attention(q, k, v, causal: bool = True):
    """Single-device flash-accumulated attention (reference oracle for
    tests and the sp=1 fast path)."""
    B, T, H, D = q.shape
    m = jnp.full((B, H, T), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((B, H, T), dtype=jnp.float32)
    o = jnp.zeros((B, T, H, D), dtype=jnp.float32)
    mask = None
    if causal:
        iq = jnp.arange(T)[:, None]
        ik = jnp.arange(T)[None, :]
        mask = jnp.where(iq >= ik, 0.0, NEG_INF)
    m, l, o = _block_attend(q, k, v, m, l, o, mask)
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype)
