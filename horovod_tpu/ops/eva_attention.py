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

The exact set runs through the flash kernels causal inside a window, a
head's windows as rows of their own; the summaries through the same
kernels under the block-causal rule (``ops/pallas_attention.BlockCausal``:
blocks of ``window`` queries over ``window / chunk`` summaries), only
visible tiles walked. Each call leaves its unmerged state
(``flash_attention_block_merged``), and the two are joined by the
online-softmax combine that ring attention joins its K/V blocks with; the
backward hands both calls the *global* row statistics
(``flash_attention_block_grads_merged``), so each set's P is normalized
over both and the two dQ add. No [T, T] or [T, T / chunk] score array
exists in either pass.

One row order for both calls. The heads are merged once a pass, q, k, v,
the summaries and dO each ``[B, T, H, D]`` -> ``[B H, T, D]`` (row ``b H +
h``), which is what the summaries' call takes; the exact set's call takes
the same arrays with a head's ``n`` windows as rows, ``[B H n, T / n, D]``
(row ``(b H + h) n + w``), a reshape of leading dimensions. So q and dO
lie in HBM once for both calls, ``lse`` and ``delta`` are handed over as
one ``[B H, T, 1]`` column array each (the exact set's ``[B H n, T / n,
1]`` is a reshape of it; the TPU compiler still writes that form by a
reshape of the rows of its own: PERF.md section 7), and the exact set's
state, dQ, dK and dV come back in the summaries' order: the merge and
``dq + dq_r`` read them where they lie.
(Cut into windows *before* the heads are merged, the exact set's rows ran
window -> head and every one of those arrays was copied between the two
orders: 1.25 GiB written a layer forward and 4.0 backward at 32 heads of
128 over 32,768 tokens.) A grouped K side could not take this order: with
fewer K/V heads than query heads the kernels send Q row ``r`` to K row
``r // group``, which needs the heads innermost in a row, and here the
windows are. EVA has every head its own key and value (``_check``;
``transformer._check_eva`` refuses ``n_kv_heads``).

The backward's two calls are ordered by an ``optimization_barrier``, the
summaries' first (``_eva_bwd`` says what for).

The scopes ``eva_local``, ``eva_remote`` (the kernels' calls of each set)
and ``eva_merge`` name the three parts on a device trace, forward and
backward. ``kernels.eva.merged_operands`` counts the differentiated
passes traced in which both calls were handed one merged q
(``docs/metrics.md``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import metrics as _metrics
from .pallas_attention import (
    _merge_heads, _split_heads, flash_attention_block_grads_merged,
    flash_attention_block_merged, merge_state, row_lse)


def _windows(x, n):
    """[B H, T, ...] -> [B H n, T / n, ...]: a head's windows as rows of
    their own, and back with ``_sequence``. Leading dimensions only:
    nothing moves. The module's docstring says why no grouped K side may
    come this way."""
    return x.reshape((x.shape[0] * n, x.shape[1] // n) + x.shape[2:])


def _sequence(x, n):
    return x.reshape((x.shape[0] // n, x.shape[1] * n) + x.shape[2:])


def _check(q, k, v, k_sum, window, chunk):
    T = q.shape[1]
    if T % window or window % chunk or k_sum.shape[1] * chunk != T:
        raise ValueError(
            f"eva_attention: {T} tokens must be whole windows of {window}, "
            f"a window whole chunks of {chunk}, and the summaries one a "
            f"chunk (got {k_sum.shape[1]})")
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"eva_attention: every head has its own key and value, as many "
            f"as queries; got q {q.shape}, k {k.shape}, v {v.shape}")


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


def _state_view(state, B, n=1):
    """A call's state as the kernels leave it (acc [B H n, T / n, D], m, l
    [B H n, T / n, 1]) in the logical layout ``_merged`` takes, acc [B, T,
    H, D] and m, l [B, H, T]: views of the head-major arrays, which the
    merge reads where they lie."""
    acc, m, l = (_sequence(x, n) for x in state)
    return (_split_heads(acc, B),
            *(x.reshape(B, -1, x.shape[1]) for x in (m, l)))


def _forward(q, k, v, k_sum, v_sum, window, chunk):
    """(o in q's type, lse float32 [B, H, T]) of the joint softmax."""
    _check(q, k, v, k_sum, window, chunk)
    B, n = q.shape[0], q.shape[1] // window
    offs = jnp.zeros((2,), jnp.int32)
    q, k, v = (_merge_heads(x) for x in (q, k, v))
    with jax.named_scope("eva_local"):
        state = _state_view(flash_attention_block_merged(
            _windows(q, n), _windows(k, n), _windows(v, n), offs,
            causal=True), B, n)
    state_r = None
    if n > 1:
        with jax.named_scope("eva_remote"):
            state_r = _state_view(flash_attention_block_merged(
                q, _merge_heads(k_sum), _merge_heads(v_sum), offs,
                causal=True, blocks=(window, window // chunk)), B)
    with jax.named_scope("eva_merge"):
        o, lse = _merged(state, state_r)  # (looked up when traced)
        return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def eva_attention(q, k, v, k_sum, v_sum, window: int, chunk: int):
    """The module's docstring on q, k, v [B, T, H, D] (rotated) and the
    chunk summaries k_sum, v_sum [B, T / chunk, H, D]: o [B, T, H, D] in
    q's type, the softmax and its statistics float32."""
    return _forward(q, k, v, k_sum, v_sum, window, chunk)[0]


def _count_pass(n):
    """A differentiated pass traced whose two calls took one merged q (and
    dO): a training step's trace counts 2 whatever traces the plain
    forward besides (a scan, a checkpoint, an evaluation), a sequence of
    one window nothing."""
    if n > 1:
        _metrics.inc("kernels.eva.merged_operands")


def _eva_fwd(q, k, v, k_sum, v_sum, window, chunk):
    o, lse = _forward(q, k, v, k_sum, v_sum, window, chunk)
    _count_pass(q.shape[1] // window)
    # Named for a caller's ``jax.checkpoint`` policy, as the flash
    # kernels' own: a layer that keeps both runs no forward kernel when it
    # is rematerialized.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, k_sum, v_sum, o, lse)


def _eva_bwd(window, chunk, res, do):
    q, k, v, k_sum, v_sum, o, lse = res
    B, T, H, _ = q.shape
    n = T // window
    offs = jnp.zeros((2,), jnp.int32)
    with jax.named_scope("eva_merge"):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1)          # [B, H, T]
    out_dtype = q.dtype
    q, k, v, do = (_merge_heads(x) for x in (q, k, v, do))
    # The columns the kernels take: the exact set's [B H n, T / n, 1] is
    # a reshape of the summaries' [B H, T, 1].
    lse, delta = (x.reshape(B * H, T, 1) for x in (lse, delta))
    # Under the global lse and delta each set's P is normalized over both
    # sets: the gradients of the two calls simply add.
    _count_pass(n)
    if n > 1:
        with jax.named_scope("eva_remote"):
            grads_r = flash_attention_block_grads_merged(
                q, _merge_heads(k_sum), _merge_heads(v_sum), do, lse, delta,
                offs, causal=True, blocks=(window, window // chunk),
                out_dtype=out_dtype)
        # The summaries' call first, the exact set's after it: the barrier
        # ties the first call's results to the operands both share, so the
        # scheduler can neither start the second call's operands early nor
        # keep both calls' results and padded statistics alive across each
        # other. Both run on the one core either way; this orders buffers'
        # lives, not work. Compiled for a v5e at 32 heads of 128 over
        # 32,768 tokens the step's temporaries read 13.945 GiB so, 14.350
        # with the exact set first and 15.247 with no barrier (14.985
        # before the two calls shared their operands).
        grads_r, (q, do, lse, delta) = lax.optimization_barrier(
            (grads_r, (q, do, lse, delta)))
    with jax.named_scope("eva_local"):
        dq, dk, dv = (_sequence(x, n) for x in
                      flash_attention_block_grads_merged(
                          *(_windows(x, n) for x in (q, k, v, do, lse,
                                                     delta)),
                          offs, causal=True, out_dtype=out_dtype))
    if n == 1:
        return (*(_split_heads(x, B) for x in (dq, dk, dv)),
                jnp.zeros_like(k_sum), jnp.zeros_like(v_sum))
    dq_r, dk_sum, dv_sum = grads_r
    with jax.named_scope("eva_merge"):
        dq = (dq.astype(jnp.float32) + dq_r.astype(jnp.float32)).astype(
            out_dtype)
    return tuple(_split_heads(x, B) for x in (dq, dk, dv, dk_sum, dv_sum))


eva_attention.defvjp(_eva_fwd, _eva_bwd)
