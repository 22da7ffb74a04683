"""EVA attention's one softmax over two key sets, on one chip.

EVA (Zheng, Yuan, Wang and Kong, "Efficient Attention via Control
Variates", arXiv:2302.04542) in the deterministic form a released model
runs: the positions fall into windows of ``window`` tokens; query ``i`` of
window ``w`` sees its own window's keys up to itself exactly, and of every
earlier window one summary key and value a chunk (``k_sum``, ``v_sum``: the
caller's pooling of each chunk of ``chunk`` tokens), none of its own
window's. Both sets share one softmax::

    o_i = (sum_{m in E_i} e^{s_im} v_m + sum_{c in R_i} e^{r_ic} v~_c) / Z_i
    Z_i = sum_E e^{s_im} + sum_R e^{r_ic},  s = q.k / sqrt(D),  r = q.k~ / sqrt(D)

The exact set runs through the flash kernels causal inside a window, the
windows as batch entries; the summaries through the same kernels under
the block-causal rule (``ops/pallas_attention.BlockCausal``: blocks of
``window`` queries over ``window / chunk`` summaries), only visible tiles
walked. Each call leaves its unmerged state (``flash_attention_block``),
and the two are joined by the online-softmax combine that ring attention
joins its K/V blocks with; the backward hands both calls the *global* row
statistics (``flash_attention_block_grads``), so each set's P is
normalized over both and the two dQ add. No [T, T] or [T, T / chunk]
score array exists in either pass.

The scopes ``eva_local``, ``eva_remote`` (the kernels' calls of each set)
and ``eva_merge`` name the three parts on a device trace, forward and
backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .pallas_attention import (
    flash_attention_block, flash_attention_block_grads, merge_state, row_lse)


def _windows(x, n):
    """[B, T, ...] -> [B n, T / n, ...]: the windows as batch entries."""
    return x.reshape((x.shape[0] * n, x.shape[1] // n) + x.shape[2:])


def _window_stat(x, n):
    """A row statistic [B, H, T] -> [B n, H, T / n], and back with
    ``_sequence_stat``."""
    B, H, T = x.shape
    return x.reshape(B, H, n, T // n).transpose(0, 2, 1, 3).reshape(
        B * n, H, T // n)


def _sequence_stat(x, n):
    Bn, H, W = x.shape
    return x.reshape(Bn // n, n, H, W).transpose(0, 2, 1, 3).reshape(
        Bn // n, H, n * W)


def _check(q, k_sum, window, chunk):
    T = q.shape[1]
    if T % window or window % chunk or k_sum.shape[1] * chunk != T:
        raise ValueError(
            f"eva_attention: {T} tokens must be whole windows of {window}, "
            f"a window whole chunks of {chunk}, and the summaries one a "
            f"chunk (got {k_sum.shape[1]})")


def _merged(state, state_r):
    """The exact set's state joined with the summaries' (None: a sequence
    of one window has none to see), each ``(acc [B, T, H, D], m, l [B, H,
    T])`` float32, and normalised: ``(o float32, lse)``. A row of the
    first window has no summary (m_r = NEG_INF, l_r = 0) and takes
    none."""
    acc, m, l = state
    if state_r is not None:
        acc_r, m_r, l_r = state_r
        m, l, acc = merge_state(m, l, acc, m_r, l_r, acc_r)
    o = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o, row_lse(m, l)


def _forward(q, k, v, k_sum, v_sum, window, chunk):
    """(o in q's type, lse float32 [B, H, T]) of the joint softmax."""
    _check(q, k_sum, window, chunk)
    n = q.shape[1] // window
    with jax.named_scope("eva_local"):
        acc, m, l = flash_attention_block(
            _windows(q, n), _windows(k, n), _windows(v, n), 0, 0,
            causal=True)
        state = (acc.reshape(q.shape), _sequence_stat(m, n),
                 _sequence_stat(l, n))
    state_r = None
    if n > 1:
        with jax.named_scope("eva_remote"):
            state_r = flash_attention_block(
                q, k_sum, v_sum, 0, 0, causal=True,
                blocks=(window, window // chunk))
    with jax.named_scope("eva_merge"):
        o, lse = _merged(state, state_r)  # (looked up when traced)
        return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def eva_attention(q, k, v, k_sum, v_sum, window: int, chunk: int):
    """The module's docstring on q, k, v [B, T, H, D] (rotated) and the
    chunk summaries k_sum, v_sum [B, T / chunk, H, D]: o [B, T, H, D] in
    q's type, the softmax and its statistics float32."""
    return _forward(q, k, v, k_sum, v_sum, window, chunk)[0]


def _eva_fwd(q, k, v, k_sum, v_sum, window, chunk):
    o, lse = _forward(q, k, v, k_sum, v_sum, window, chunk)
    # Named for a caller's ``jax.checkpoint`` policy, as the flash
    # kernels' own: a layer that keeps both runs no forward kernel when it
    # is rematerialized.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, k_sum, v_sum, o, lse)


def _eva_bwd(window, chunk, res, do):
    q, k, v, k_sum, v_sum, o, lse = res
    n = q.shape[1] // window
    with jax.named_scope("eva_merge"):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1)          # [B, H, T]
    # Under the global lse and delta each set's P is normalized over both
    # sets: the gradients of the two calls simply add.
    with jax.named_scope("eva_local"):
        dq, dk, dv = flash_attention_block_grads(
            _windows(q, n), _windows(k, n), _windows(v, n), _windows(do, n),
            _window_stat(lse, n), _window_stat(delta, n), 0, 0, causal=True,
            out_dtype=q.dtype)
        dq, dk, dv = (x.reshape(q.shape) for x in (dq, dk, dv))
    if n == 1:
        return dq, dk, dv, jnp.zeros_like(k_sum), jnp.zeros_like(v_sum)
    with jax.named_scope("eva_remote"):
        dq_r, dk_sum, dv_sum = flash_attention_block_grads(
            q, k_sum, v_sum, do, lse, delta, 0, 0, causal=True,
            blocks=(window, window // chunk), out_dtype=q.dtype)
    with jax.named_scope("eva_merge"):
        dq = (dq.astype(jnp.float32) + dq_r.astype(jnp.float32)).astype(
            q.dtype)
    return dq, dk, dv, dk_sum, dv_sum


eva_attention.defvjp(_eva_fwd, _eva_bwd)
