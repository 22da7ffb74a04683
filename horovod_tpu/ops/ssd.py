"""The state-space-duality scan of a Mamba-2 layer, chunked (matmul) form.

Per head ``h`` (P channels, state N), with one group of ``B`` and ``C``
shared by all heads (Dao & Gu, arXiv:2405.21060; HF
``GraniteMoeHybridMambaLayer``), over the tokens ``t`` of one sequence::

    a_t = dt_t * A                       (A = -exp(A_log) < 0, dt_t > 0)
    S_t = exp(a_t) S_{t-1} + dt_t x_t (x) B_t          S [P, N], S_{-1} = 0
    y_t = S_t C_t + D x_t

The chunked derivation. Cut the sequence into chunks of Q tokens and
write ``cum_i`` for the sum of ``a`` from the chunk's first token through
``i``. Unrolling the recurrence inside a chunk that starts from the state
``S_prev`` gives ``S_i = exp(cum_i) S_prev + sum_{j<=i} exp(cum_i - cum_j)
dt_j x_j (x) B_j``, so

    y_i = sum_{j<=i} L_ij (C_i . B_j) dt_j x_j  +  exp(cum_i) (S_prev C_i)
    L_ij = exp(cum_i - cum_j) for j <= i, else 0

— a ``[Q, Q]`` masked matmul a head (``(L o C B^T)(dt x)``) plus the
carried state read through ``C``. A chunk's own contribution to the state
at its end is ``S_c = sum_j exp(cum_Q - cum_j) dt_j x_j (x) B_j`` (a
``[P, Q] x [Q, N]`` matmul), and the states carry from chunk to chunk by
``S_prev(c) = exp(cum_Q(c-1)) S_prev(c-1) + S_{c-1}``: T/Q steps of the
first recurrence, written below as one small lower-triangular matmul over
the chunks with the decays ``exp(sum_{z<k<c} cum_Q(k))``.

What is float32: ``dt``, ``A``, ``a``, both cumulative sums, every decay
(``L``, the chunk-end and chunk-to-chunk factors), the chunk states and
their carry (the carry's matmul at ``highest`` precision) and every
matmul's accumulator. The matmuls' operands — ``C B^T``; ``L o C B^T o
dt`` against ``x``; the decayed ``dt x`` against ``B``; ``C`` against the
carried state — are in ``x``'s type (bf16 in training, float32 where the
model is float32).

The ``[heads, Q, Q]`` decay and score arrays (64 KB a token and layer in
float32 at 64 heads and Q 256) are no residuals of the backward pass: the
whole function is under ``jax.checkpoint`` and keeps its arguments only.
Everything is XLA einsums; there is no kernel here, on or off the chip.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """``y`` [b, T, H, P] in ``x``'s type of the recurrence above.

    x [b, T, H, P]; dt [b, T, H] float32, positive (after the softplus);
    A [H] float32, negative; B, C [b, T, N] (one group); D [H] float32.
    A length that ``chunk`` does not divide is padded with tokens of
    ``dt = 0`` (decay 1, no input), which no earlier token sees."""
    T = x.shape[1]
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] *
                               (v.ndim - 2)) for v in (x, dt, B, C))
    return _ssd(x, dt, A, B, C, D, chunk)[:, :T]


def _masked_exp(diff, keep):
    """``exp(diff)`` where ``keep``, 0 elsewhere; the masked entries are
    -inf before the ``exp`` so that neither pass sees an overflow."""
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd(x, dt, A, B, C, D, Q):
    b, T, H, P = x.shape
    N, nc, f32 = B.shape[-1], T // Q, jnp.float32
    xc = x.reshape(b, nc, Q, H, P)
    Bc, Cc = B.reshape(b, nc, Q, N), C.reshape(b, nc, Q, N)
    # [b, nc, H, Q]: heads ahead of the chunk's tokens, as the masked
    # matmul's batch dimensions want them.
    dtc = dt.reshape(b, nc, Q, H).transpose(0, 1, 3, 2)
    cum = jnp.cumsum(dtc * A[:, None], axis=-1)
    total = cum[..., -1]  # [b, nc, H]

    # Inside a chunk: (L o C B^T o dt) x.
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                        preferred_element_type=f32)
    mask = (_masked_exp(cum[..., :, None] - cum[..., None, :], lower)
            * scores[:, :, None] * dtc[..., None, :])
    y = jnp.einsum("bchij,bcjhp->bcihp", mask.astype(x.dtype), xc,
                   preferred_element_type=f32)

    # A chunk's own state at its end, and the states carried to each
    # chunk's start.
    to_end = (jnp.exp(total[..., None] - cum) * dtc).transpose(0, 1, 3, 2)
    states = jnp.einsum("bcjhp,bcjn->bchpn",
                        (xc * to_end[..., None]).astype(x.dtype), Bc,
                        preferred_element_type=f32)
    through = jnp.cumsum(total, axis=1)  # chunks 0..c, [b, nc, H]
    before = (through - total).transpose(0, 2, 1)  # chunks 0..c-1
    carry = _masked_exp(
        before[..., :, None] - through.transpose(0, 2, 1)[..., None, :],
        jnp.tril(jnp.ones((nc, nc), bool), -1))  # [b, H, c, z], z < c
    carried = jnp.einsum("bhcz,bzhpn->bchpn", carry, states,
                         precision=lax.Precision.HIGHEST)
    y = y + (jnp.einsum("bcin,bchpn->bcihp", Cc, carried.astype(x.dtype),
                        preferred_element_type=f32)
             * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None])
    y = y + D[:, None] * xc.astype(f32)
    return y.astype(x.dtype).reshape(b, T, H, P)
