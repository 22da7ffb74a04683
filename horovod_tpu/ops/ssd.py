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
their carry (the carry's matmul at ``highest`` precision), the masked
tile ``L o C B^T o dt`` before its cast, and every matmul's accumulator.
The matmuls' operands — ``C B^T``; ``L o C B^T o dt`` against ``x``; the
decayed ``dt x`` against ``B``; ``C`` against the carried state — are in
``x``'s type (bf16 in training, float32 where the model is float32).

Two parts. The sums and the states stay XLA's: both cumulative sums, the
chunk states and their carry (``_ssd``; small arrays and two matmuls).
What a chunk's tokens read — the masked matmul, the carried state through
``C``, ``D x`` — is a Pallas kernel pair behind one ``custom_vjp``
(``ssd_fwd``, ``ssd_bwd``: the ``name`` of each ``pallas_call``, which jax
writes as a scope into the custom call's ``op_name``): a grid step is one
chunk and a group of heads, and the ``[heads, Q, Q]`` decay and score
arrays exist only in VMEM, in both passes; the backward forms the tile
anew from the saved inputs. The kernels take ``x`` and give ``y`` as
``[b, H P, T]``, the tokens minor: the layout XLA keeps the mixer's
activations in around the scan on a v5e (a ``[b, T, H P]`` operand cost a
transposing copy of ``x``, ``y``, ``dy`` and ``dx`` a layer; PERF.md
section 6, PR 31), in which a head's channels are 64 rows of a block and
no product but ``dy x^T`` wants a transpose. ``kernel_plan`` decides
heads a step and the tile's strips from the shape alone.
``_pallas_attention._resolve_dispatch`` decides as for
the flash kernels: Mosaic on the chip, interpreted under
``HVD_PALLAS_INTERPRET=1``, else — and for a shape the plan refuses — the
same term as XLA einsums (``_chunks_xla``, under ``jax.checkpoint`` so
that the ``[heads, Q, Q]`` arrays are no residuals there either: 64 KB a
token and layer in float32 at 64 heads and Q 256).
"""

import functools
from typing import NamedTuple

from ..common import metrics as _metrics

# The first of a decoder job's imports to reach jax.experimental.pallas,
# which brings every backend's lowering with it (docs/diagnostics.md,
# "Set-up spans").
with _metrics.span("import:horovod_tpu.ops.ssd"):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..common import logging as _log
    from ..common.compat import (
        pallas_tpu_compiler_params as _compiler_params)
    from . import pallas_attention as _pallas_attention
    from .pallas_attention import _across, _mxu_dot

_LANES = 128
# Heads unrolled into one loop body: the heads of one 128-lane slab of x.
# Every head of a body is traced and lowered to Mosaic at every start
# (PERF.md section 6, PR 27), so a head narrower than 32 channels is the
# einsum form's.
_MAX_BODY = 4
# Strips of the [Q, Q] tile that a head's walk unrolls (below).
_MAX_STRIPS = 4


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


def _ssd(x, dt, A, B, C, D, Q):
    b, T, H, P = x.shape
    N, nc, f32 = B.shape[-1], T // Q, jnp.float32
    xc = x.reshape(b, nc, Q, H, P)
    Bc = B.reshape(b, nc, Q, N)
    # [b, nc, H, Q]: heads ahead of the chunk's tokens, as the masked
    # matmul's batch dimensions (and the kernels' row vectors) want them.
    dtc = dt.reshape(b, nc, Q, H).transpose(0, 1, 3, 2)
    cum = jnp.cumsum(dtc * A[:, None], axis=-1)
    total = cum[..., -1]  # [b, nc, H]

    # A chunk's own state at its end, and the states carried to each
    # chunk's start. x goes to the chunk's tokens minor in its own type,
    # behind a barrier, before the float32 product: XLA contracts over the
    # minor dimension, and left to itself it widens x to float32 first
    # and transposes that (three passes over a float32 [b, T, H, P] a
    # layer and direction; PERF.md section 6, PR 31).
    to_end = jnp.exp(total[..., None] - cum) * dtc  # [b, nc, H, Q]
    weighted = (lax.optimization_barrier(xc.transpose(0, 1, 3, 4, 2))
                * to_end[..., None, :]).astype(x.dtype)  # [b, nc, H, P, Q]
    states = jnp.einsum("bcjhp,bcjn->bchpn",
                        weighted.transpose(0, 1, 4, 2, 3), Bc,
                        preferred_element_type=f32)
    through = jnp.cumsum(total, axis=1)  # chunks 0..c, [b, nc, H]
    before = (through - total).transpose(0, 2, 1)  # chunks 0..c-1
    carry = _masked_exp(
        before[..., :, None] - through.transpose(0, 2, 1)[..., None, :],
        jnp.tril(jnp.ones((nc, nc), bool), -1))  # [b, H, c, z], z < c
    carried = jnp.einsum("bhcz,bzhpn->bchpn", carry, states,
                         precision=lax.Precision.HIGHEST).astype(x.dtype)

    # What a chunk's tokens read: kernels or einsums.
    use_pallas, interpret = _pallas_attention._resolve_dispatch(None)
    if use_pallas and kernel_plan(H, P, N, Q, x.dtype) is not None:
        y = _chunks_pallas(x.reshape(b, T, H * P).transpose(0, 2, 1), B, C,
                           dtc, cum, carried.reshape(b, nc, H * P, N), D, P,
                           interpret)
        return y.transpose(0, 2, 1).reshape(b, T, H, P)
    return _chunks_xla(x, B, C, dtc, cum, carried, D)


@jax.checkpoint
def _chunks_xla(x, B, C, dtc, cum, carried, D):
    """Inside a chunk, ``(L o C B^T o dt) x``; the carried state read
    through ``C``; ``D x``. Keeps its arguments only."""
    b, nc, H, Q = cum.shape
    T, P, N, f32 = x.shape[1], x.shape[-1], B.shape[-1], jnp.float32
    xc = x.reshape(b, nc, Q, H, P)
    Bc, Cc = B.reshape(b, nc, Q, N), C.reshape(b, nc, Q, N)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                        preferred_element_type=f32)
    mask = (_masked_exp(cum[..., :, None] - cum[..., None, :], lower)
            * scores[:, :, None] * dtc[..., None, :])
    y = jnp.einsum("bchij,bcjhp->bcihp", mask.astype(x.dtype), xc,
                   preferred_element_type=f32)
    y = y + (jnp.einsum("bcin,bchpn->bcihp", Cc, carried,
                        preferred_element_type=f32)
             * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None])
    y = y + D[:, None] * xc.astype(f32)
    return y.astype(x.dtype).reshape(b, T, H, P)


# ---------------------------------------------------------------------------
# The plan: heads a grid step, heads a loop body, the tile's strips.
# ---------------------------------------------------------------------------


class ScanPlan(NamedTuple):
    """What the ``pallas_call`` of a pass does, all of it static."""
    heads: int       # heads a grid step (a [heads P, Q] block of x)
    body: int        # heads of one loop body
    slab: int        # rows of x a loop body reads: body * P
    strip: int       # rows of a strip of the [Q, Q] tile (below)
    vmem_bytes: int  # counted VMEM; ``vmem_limit_bytes`` is set from it


def _vmem_bytes(kind, heads, H, P, N, Q, slab, strip, itemsize):
    """VMEM one grid step of pass ``kind`` holds: pipelined blocks twice,
    the scratch, and a dozen float32 [strip, Q] temporaries a head of a
    loop body."""
    wide = heads * P * Q * itemsize                       # x, y | dy, dx
    state = heads * P * N * itemsize                      # S_prev | dS
    rows = 3 * heads * Q * 4 + Q * max(H, _LANES) * 4     # dt, cum, D; cum^T
    narrow = 2 * Q * N * itemsize                         # B, C
    column = (slab // P) * Q * _LANES * 4
    if kind == "fwd":
        blocks = 2 * wide + state + rows + narrow
        scratch = Q * Q * 4 + column
    else:
        blocks = 3 * wide + 2 * state + 2 * rows + 2 * narrow
        scratch = (2 * Q * Q * 4 + 2 * Q * N * 4 + column
                   + slab * Q * (4 + itemsize))
    return 2 * blocks + scratch + 12 * (slab // P) * strip * Q * 4


def kernel_plan(H, P, N, Q, dtype, *, kind="bwd"):
    """The grid step of pass ``kind`` ("fwd" or "bwd") for ``H`` heads of
    ``P`` channels, state ``N`` and chunk ``Q`` in ``dtype``: a pure
    function of the shape. None where the kernels do not take the shape
    and the einsum form does: a chunk or a state off the lane grid (128),
    heads that do not stack into 128-row slabs (P neither 32 nor 64 nor a
    multiple of 128), more than eight heads that sublane tiles of eight
    do not divide, or no group of heads that fits ``VMEM_BUDGET``.

    Heads a step: all of them where that fits, else the largest divisor
    of ``H`` in whole slabs and sublane tiles that does; the step's heads
    are a loop inside the kernel, the heads of one slab of ``x`` (two at P
    64) a loop body, so that one head's matmul runs under another's
    ``exp``. Strips: the ``[Q, Q]`` tile of a head as strips of 128 rows,
    strip ``r`` holding the columns ``[0, 128 (r + 1))`` that its rows can
    see (3 of 4 sub-tiles at Q 256), where that makes at most
    ``_MAX_STRIPS``; else one strip, the whole tile."""
    itemsize = jnp.dtype(dtype).itemsize
    if Q % _LANES or N % _LANES:
        return None
    if P % _LANES and (_LANES % P or _LANES // P > _MAX_BODY):
        return None
    slab = max(P, _LANES)
    body = slab // P
    strip = _LANES if 1 < Q // _LANES <= _MAX_STRIPS else Q
    for heads in range(H, 0, -1):
        if H % heads or heads % body or (
                (heads != H or heads > 8) and heads % 8):
            continue
        vmem = _vmem_bytes(kind, heads, H, P, N, Q, slab, strip, itemsize)
        if vmem <= _pallas_attention.VMEM_BUDGET:
            return ScanPlan(heads, body, slab, strip, vmem)
    return None


def _log_plan(kind, shape, dtype, plan):
    """Everything a plan decides is static, so it is logged once, when
    the call is traced (``HOROVOD_LOG_LEVEL=debug``), and counted: the
    host traces this ``pallas_call`` and lowers it to Mosaic."""
    _metrics.inc(f"kernels.traced.ssd_{kind}")
    _log.debug(
        f"ssd_{kind} {tuple(shape)} {jnp.dtype(dtype).name}: "
        f"{plan.heads} heads a step ({plan.body} a loop body of "
        f"{plan.slab} rows), strips of {plan.strip} rows, VMEM "
        f"{plan.vmem_bytes} B")


# ---------------------------------------------------------------------------
# The kernels. Refs of a grid step (one chunk, ``plan.heads`` heads), with
# the chunk's tokens along the lanes wherever a head's channels are the
# rows: x, y, dy, dx [heads P, Q]; S_prev, dS [heads P, N]; B, C, dB, dC
# [Q, N]; dt, cum, D, ddt, dD as rows [heads, Q]; cum and its gradient
# also as columns [Q, H] (all heads: a block's last dimension is whole or
# a multiple of 128). A head's tile is [i, j]: token i a row, j a lane.
# ---------------------------------------------------------------------------


def _strips(plan, Q):
    """(rows of the strip, its columns) from the widest strip down, so
    that a sum over strips starts with the one that covers every
    column."""
    t = plan.strip
    return [(slice(r * t, (r + 1) * t), (r + 1) * t)
            for r in reversed(range(Q // t))]


def _strip_keep(rows, cols):
    """Which of the strip's [rows, cols] entries have ``j <= i``."""
    t = rows.stop - rows.start
    return (rows.start + lax.broadcasted_iota(jnp.int32, (t, cols), 0) >=
            lax.broadcasted_iota(jnp.int32, (t, cols), 1))


def _scores(c_ref, b_ref, g_ref, plan):
    """``C B^T`` of the chunk, once for all heads, strip by strip."""
    for rows, cols in _strips(plan, g_ref.shape[0]):
        g_ref[rows, :cols] = _mxu_dot(c_ref[rows, :], b_ref[:cols, :],
                                      ((1,), (1,)))


def _fill_columns(col_ref, cumt_ref, first, body):
    """``col_ref[r]`` becomes head ``first + r``'s column of the [Q, H]
    block, 128 lanes wide with the same value in every lane (as the flash
    kernels keep row statistics): ``first`` is a loop index, and the lane
    a head names is found by a select."""
    block = cumt_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    for r in range(body):
        col = jnp.sum(jnp.where(lane == first + r, block, 0.0), axis=1,
                      keepdims=True)
        col_ref[r] = jnp.broadcast_to(col, (block.shape[0], _LANES))


def _row_group(plan, first):
    """Where a body's heads lie in the [heads, Q] row blocks: (rows of
    the sublane tile that holds them, the first head's place in it). A
    single row at a loop index is no aligned load, a tile of eight is."""
    group = min(8, plan.heads)
    if group == plan.heads:
        return slice(0, group), first
    start = pl.multiple_of(first // group * group, group)
    return pl.ds(start, group), first - start


def _row(ref, group, at, lanes):
    """Row ``at`` of the row group over ``lanes``, [1, lanes]."""
    block = ref[group, lanes]
    sub = lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(sub == at, block, 0.0), axis=0, keepdims=True)


def _set_row(ref, group, at, lanes, value, add):
    """Row ``at`` of the row group over ``lanes`` becomes ``value`` (or
    grows by it)."""
    old = ref[group, lanes]
    sub = lax.broadcasted_iota(jnp.int32, old.shape, 0)
    ref[group, lanes] = jnp.where(sub == at, old + value if add else value,
                                  old)


def _channels(plan, P, k, r):
    """The rows of x that head ``r`` of loop body ``k`` owns."""
    return pl.ds(pl.multiple_of(k * plan.slab + r * P, P), P)


def _decay_tile(col, cols, keep, cum_j, g, dt_j):
    """A strip of one head's tile in float32: ``L`` (the decays, masked
    before the ``exp``), ``L o C B^T`` and that times ``dt_j``."""
    decay = _masked_exp(_across(col, cols) - cum_j, keep)
    scored = decay * g
    return decay, scored, scored * dt_j


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumt_ref, s_ref,
                d_ref, y_ref, g_ref, col_ref, *, plan: ScanPlan, P: int):
    """``y^T = x^T M^T + exp(cum_i) (S_prev C^T) + D x^T`` a head, strip
    by strip: the tile ``M`` is formed in float32, cast once, and is the
    matmul's right side as it stands; one write of ``y`` in ``x``'s
    type."""
    Q, f32 = x_ref.shape[1], jnp.float32
    strips = _strips(plan, Q)
    keeps = [_strip_keep(rows, cols) for rows, cols in strips]
    head0 = pl.program_id(2) * plan.heads
    _scores(c_ref, b_ref, g_ref, plan)

    def body(k):
        group, at = _row_group(plan, k * plan.body)
        _fill_columns(col_ref, cumt_ref, head0 + k * plan.body, plan.body)
        for (rows, cols), keep in zip(strips, keeps):
            for r in range(plan.body):
                mine = _channels(plan, P, k, r)
                tile = _decay_tile(
                    col_ref[r, rows, :], cols, keep,
                    _row(cum_ref, group, at + r, slice(0, cols)),
                    g_ref[rows, :cols],
                    _row(dt_ref, group, at + r, slice(0, cols)))[2]
                y = _mxu_dot(x_ref[mine, :cols], tile.astype(x_ref.dtype),
                             ((1,), (1,)))
                y += (jnp.exp(_row(cum_ref, group, at + r, rows))
                      * _mxu_dot(s_ref[mine, :], c_ref[rows, :],
                                 ((1,), (1,))))
                y += (_row(d_ref, group, at + r, rows)
                      * x_ref[mine, rows].astype(f32))
                y_ref[mine, rows] = y.astype(y_ref.dtype)

    _pallas_attention._for_each(plan.heads // plan.body, body)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, dt_ref, cum_ref, cumt_ref,
                s_ref, d_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcumt_ref,
                ds_ref, dd_ref, g_ref, col_ref, dg_ref, db_acc, dc_acc,
                dyn_ref, dxt_acc, *, plan: ScanPlan, P: int):
    """From ``dy`` and the forward's inputs: ``dx``; ``dS_prev``; ``dD``
    (a lane a token, summed outside); ``ddt`` as rows — the tile's column
    sums ``sum_i dM_ij L_ij G_ij``; the decay sums' gradient by ``i`` as
    columns — the row sums ``sum_j dM_ij M_ij`` and the carried read's
    ``sum_p dy_ip exp(cum_i) W_ip`` (its part by ``j`` is ``-dt_j ddt_j``,
    taken outside); and, with the score gradient ``sum_h dM L dt_j``
    summed over the chunk's heads in scratch (the last grid dimension
    walks the head groups), ``dB`` and ``dC`` in the last group's step."""
    Q, f32 = x_ref.shape[1], jnp.float32
    strips = _strips(plan, Q)
    keeps = [_strip_keep(rows, cols) for rows, cols in strips]
    step, n_steps = pl.program_id(2), pl.num_programs(2)
    head0 = step * plan.heads
    _scores(c_ref, b_ref, g_ref, plan)

    @pl.when(step == 0)
    def _():
        for rows, cols in strips:
            dg_ref[rows, :cols] = jnp.zeros((plan.strip, cols), f32)
        db_acc[...] = jnp.zeros(db_acc.shape, f32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, f32)
        dcumt_ref[...] = jnp.zeros(dcumt_ref.shape, f32)

    def body(k):
        group, at = _row_group(plan, k * plan.body)
        slab = pl.ds(pl.multiple_of(k * plan.slab, plan.slab), plan.slab)
        first = head0 + k * plan.body
        _fill_columns(col_ref, cumt_ref, first, plan.body)
        # dy with its tokens as rows, once a slab: the left side of dM =
        # dy x^T and of the carried read's products. Every other product
        # takes dy and x as they lie, tokens along the lanes.
        dyn_ref[...] = dy_ref[slab, :].astype(f32).T.astype(dyn_ref.dtype)
        owner = lax.broadcasted_iota(
            jnp.int32, (plan.strip, plan.slab), 1) // P
        lane_h = lax.broadcasted_iota(
            jnp.int32, (plan.strip, dcumt_ref.shape[1]), 1)
        for r in range(plan.body):
            mine = _channels(plan, P, k, r)
            dy, x = dy_ref[mine, :].astype(f32), x_ref[mine, :].astype(f32)
            decay_i = jnp.exp(_row(cum_ref, group, at + r, slice(None)))
            # dS = (exp(cum_i) dy)^T C, dD = sum dy x, dx = D dy + ...
            ds_ref[mine, :] = _mxu_dot(
                (dy * decay_i).astype(x_ref.dtype), c_ref[...],
                ((1,), (0,))).astype(ds_ref.dtype)
            _set_row(dd_ref, group, at + r, slice(None),
                     jnp.sum(dy * x, axis=0, keepdims=True), add=False)
            dxt_acc[r * P:(r + 1) * P, :] = (
                _row(d_ref, group, at + r, slice(None)) * dy)
        for (rows, cols), keep in zip(strips, keeps):
            # The carried read exp(cum_i) (C S^T), tokens as rows: dW for
            # dC, and its row sums for the decay sums.
            column = _across(col_ref[0, rows, :], plan.slab)
            for r in range(1, plan.body):
                column = jnp.where(owner == r, _across(col_ref[r, rows, :],
                                                       plan.slab), column)
            read = dyn_ref[rows, :].astype(f32) * jnp.exp(column)
            dc_acc[rows, :] += _mxu_dot(read.astype(x_ref.dtype),
                                        s_ref[slab, :], ((1,), (0,)))
            off = read * _mxu_dot(c_ref[rows, :], s_ref[slab, :],
                                  ((1,), (1,)))
            for r in range(plan.body):
                mine = _channels(plan, P, k, r)
                dy_rows = dyn_ref[rows, :]
                if plan.body > 1:
                    dy_rows = jnp.where(owner == r, dy_rows,
                                        jnp.zeros_like(dy_rows))
                dt_j = _row(dt_ref, group, at + r, slice(0, cols))
                decay, scored, tile = _decay_tile(
                    col_ref[r, rows, :], cols, keep,
                    _row(cum_ref, group, at + r, slice(0, cols)),
                    g_ref[rows, :cols], dt_j)
                # dx^T = dy^T M: the tile is the right side as it stands.
                dxt_acc[r * P:(r + 1) * P, :cols] += _mxu_dot(
                    dy_ref[mine, rows], tile.astype(x_ref.dtype),
                    ((1,), (0,)))
                d_tile = _mxu_dot(dy_rows, x_ref[slab, :cols],
                                  ((1,), (0,)))                   # dM
                dg_ref[rows, :cols] += d_tile * decay * dt_j
                by_dt = d_tile * scored
                _set_row(ddt_ref, group, at + r, slice(0, cols),
                         jnp.sum(by_dt, axis=0, keepdims=True),
                         add=cols < Q)
                row = jnp.sum(by_dt * dt_j, axis=1, keepdims=True)
                row += jnp.sum(jnp.where(owner == r, off, 0.0), axis=1,
                               keepdims=True)
                dcumt_ref[rows, :] = jnp.where(lane_h == first + r, row,
                                               dcumt_ref[rows, :])
        dx_ref[slab, :] = dxt_acc[...].astype(dx_ref.dtype)

    _pallas_attention._for_each(plan.heads // plan.body, body)

    @pl.when(step == n_steps - 1)
    def _():
        for rows, cols in strips:
            dg = dg_ref[rows, :cols].astype(x_ref.dtype)
            dc_acc[rows, :] += _mxu_dot(dg, b_ref[:cols, :], ((1,), (0,)))
            db_acc[:cols, :] += _mxu_dot(dg, c_ref[rows, :], ((0,), (0,)))
        db_ref[...] = db_acc[...].astype(db_ref.dtype)
        dc_ref[...] = dc_acc[...].astype(dc_ref.dtype)


def _specs(plan, H, P, N, Q):
    """Block specs by the operand's kind, over the grid (b, chunk, head
    group)."""
    wide = plan.heads * P
    return {
        "wide": pl.BlockSpec((None, wide, Q), lambda b, c, g: (b, g, c)),
        "narrow": pl.BlockSpec((None, Q, N), lambda b, c, g: (b, c, 0)),
        "rows": pl.BlockSpec((None, None, plan.heads, Q),
                             lambda b, c, g: (b, c, g, 0)),
        "columns": pl.BlockSpec((None, Q, H), lambda b, c, g: (b, c, 0)),
        "state": pl.BlockSpec((None, None, wide, N),
                              lambda b, c, g: (b, c, g, 0)),
        "head_rows": pl.BlockSpec((plan.heads, Q), lambda b, c, g: (g, 0)),
    }


def _ssd_call(kind, kernel, plan, grid, in_specs, out_specs, out_shape,
              scratch, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(_pallas_attention._VMEM_DEFAULT_LIMIT,
                                 plan.vmem_bytes + (8 << 20))),
        interpret=interpret,
        name=f"ssd_{kind}",
    )


def _operands(x, B, C, dtc, cum, carried, D):
    """The forward's operands in the kernels' order and layouts."""
    b, nc, H, Q = cum.shape
    cumt = cum.transpose(0, 1, 3, 2).reshape(b, nc * Q, H)
    return (x, B, C, dtc, cum, cumt, carried,
            jnp.broadcast_to(D[:, None], (H, Q)))


_IN_KINDS = ("wide", "narrow", "narrow", "rows", "rows", "columns", "state",
             "head_rows")


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chunks_pallas(x, B, C, dtc, cum, carried, D, P, interpret):
    """``_chunks_xla`` as the kernel pair, on x [b, H P, T] (the tokens
    minor, as XLA keeps the mixer's activations around the scan), dtc and
    cum [b, nc, H, Q] and carried [b, nc, H P, N]; y as x."""
    return _chunks_fwd(x, B, C, dtc, cum, carried, D, P, interpret)[0]


def _chunks_fwd(x, B, C, dtc, cum, carried, D, P, interpret):
    b, nc, H, Q = cum.shape
    N = B.shape[-1]
    plan = kernel_plan(H, P, N, Q, x.dtype, kind="fwd")
    _log_plan("fwd", x.shape, x.dtype, plan)
    specs = _specs(plan, H, P, N, Q)
    y = _ssd_call(
        "fwd", functools.partial(_fwd_kernel, plan=plan, P=P), plan,
        (b, nc, H // plan.heads), [specs[k] for k in _IN_KINDS],
        specs["wide"], jax.ShapeDtypeStruct(x.shape, x.dtype),
        [pltpu.VMEM((Q, Q), jnp.float32),
         pltpu.VMEM((plan.body, Q, _LANES), jnp.float32)], interpret,
    )(*_operands(x, B, C, dtc, cum, carried, D))
    return y, (x, B, C, dtc, cum, carried, D)


def _chunks_bwd(P, interpret, residuals, dy):
    x, B, C, dtc, cum, carried, D = residuals
    b, nc, H, Q = cum.shape
    N, f32 = B.shape[-1], jnp.float32
    plan = kernel_plan(H, P, N, Q, x.dtype, kind="bwd")
    _log_plan("bwd", x.shape, x.dtype, plan)
    specs = _specs(plan, H, P, N, Q)
    operands = _operands(x, B, C, dtc, cum, carried, D)
    out_kinds = ("wide", "narrow", "narrow", "rows", "columns", "state",
                 "rows")
    rows = jax.ShapeDtypeStruct(cum.shape, f32)
    shapes = (x, B, C, rows, jax.ShapeDtypeStruct(operands[5].shape, f32),
              carried, rows)
    dx, dB, dC, ddt, dcumt, dS, dD = _ssd_call(
        "bwd", functools.partial(_bwd_kernel, plan=plan, P=P), plan,
        (b, nc, H // plan.heads),
        [specs["wide"]] + [specs[k] for k in _IN_KINDS],
        [specs[k] for k in out_kinds],
        [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in shapes],
        [pltpu.VMEM((Q, Q), f32), pltpu.VMEM((plan.body, Q, _LANES), f32),
         pltpu.VMEM((Q, Q), f32), pltpu.VMEM((Q, N), f32),
         pltpu.VMEM((Q, N), f32), pltpu.VMEM((Q, plan.slab), x.dtype),
         pltpu.VMEM((plan.slab, Q), f32)], interpret,
    )(operands[0], dy, *operands[1:])
    dcum = dcumt.reshape(b, nc, Q, H).transpose(0, 1, 3, 2) - dtc * ddt
    return dx, dB, dC, ddt, dcum, dS, dD.sum((0, 1, 3))


_chunks_pallas.defvjp(_chunks_fwd, _chunks_bwd)
