"""Kimi Delta Attention's scan, chunked (matmul) form.

Per head (keys of K channels, values of V, a state ``S`` [K, V]), over the
tokens ``t`` of one sequence, the gated delta rule with a decay per channel
(Kimi Linear, arXiv:2510.26692 section 3)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                 S_0 = 0, g_t <= 0

The chunked derivation. Cut the sequence into chunks of C tokens, write
``G_r`` [K] for the sum of ``g`` from the chunk's first token through ``r``
and ``S_0`` for the state the chunk starts from. With ``u_r = beta_r (v_r -
(Diag(exp(g_r)) S_{r-1})^T k_r)``, what token ``r`` writes that the state
did not already predict, the recurrence is ``S_r = Diag(exp(g_r)) S_{r-1} +
k_r u_r^T``, which unrolls to ``S_r = Diag(exp(G_r)) S_0 + sum_{j<=r}
Diag(exp(G_r - G_j)) k_j u_j^T``. Putting that into ``u_r`` gives a unit
lower-triangular system over the chunk's tokens::

    M(X, Y)_rj = sum_c X_r[c] Y_j[c] exp(G_r[c] - G_j[c])
    A = Diag(beta) strict_tril(M(K, K))                       [C, C]
    (I + A) U = Diag(beta) (V - (K o exp(G)) S_0)             [C, V]
    O = (Q o exp(G)) S_0 + tril(M(Q, K)) U
    S_C = Diag(exp(G_C)) S_0 + (K o exp(G_C - G))^T U

— every line a matmul but the solve. ``(I + A)^-1`` is formed exactly, by
matmuls too (``_unit_lower_inverse``): the diagonal blocks of ``sub`` rows
are nilpotent of order ``sub``, so ``(I + A_d)^-1 = (I - A_d)(I + A_d^2)(I
+ A_d^4)...``, all blocks at once as one block-diagonal [C, C] matrix; what
is left, ``N = (I + A_d)^-1 A_off``, is nilpotent of order C / sub over the
blocks and goes the same way. (The product over the whole chunk at once
would square entries up to C-choose-C/2 where keys repeat.)

The decay per channel is why ``M`` is no single matmul: ``exp(G_r - G_j)``
is at most one, but ``exp(G_r) exp(-G_j)`` overflows float32 once a channel
has decayed by e^88. ``M`` is formed a block of ``sub`` rows at a time
(``_Blocks``) around the sum at the block's start, ``ref``: ``(X_r o exp(G_r
- ref)) . (Y_j o exp(ref - G_j))``. For a column of an earlier block both
factors are at most one; inside the block the second is at most ``exp(-sub
min g)``, which is why the caller's gate is bounded below: with ``g >= -5``
and ``sub`` 16 that is e^80 (``_CAP``; entries above the diagonal, masked,
are clipped there).

What is float32: ``g`` and its sums, every decay, ``beta``, ``A`` and its
inverse (matmuls at ``highest``), the state, its carry from chunk to chunk
and every accumulator. The other matmuls' operands — keys and queries under
their decays, values, ``U``, the state where it is read — are in ``q``'s
type (bf16 in training, float32 where the model is float32).

Reverse mode is written out (``_chunk_backward``: the transpose of each
line above, the same blocks around the same ``ref``), a second walk over
the chunks from the last to the first that carries the state's gradient
and reads the state each chunk started from, which the forward walk kept
([nc, V, K] float32 a head, transposed so that a decay scales lanes).

Two drivers run the same chunk mathematics: a ``lax.scan`` over the chunks
under ``vmap`` over sequences and heads (XLA), and a Pallas kernel pair
(``kda_fwd``, ``kda_bwd``: the ``name`` of each ``pallas_call``, which jax
writes as a scope into the custom call's ``op_name``) whose grid is
(sequence, heads of a step, chunk), the chunks sequential with the states
in VMEM scratch, the operands read where they lie in ``[b, T, H K]``. A
grid step holds one chunk of each of ``ScanPlan.heads`` neighbouring heads
(four where they divide ``H`` and fit the VMEM budget, else two, else
one), the chunk mathematics over a leading head axis: a float32 product at
``highest`` is six bf16 passes, which fall on the chip's four MXUs as one
full round and one half-empty, and each product of the solve waits for the
one before it, so one head's chunk leaves the MXUs a third idle; the heads
are independent, and their passes fill the rounds (PERF.md section 6,
PR 48).
``_pallas_attention._resolve_dispatch`` decides as for the flash kernels:
Mosaic on the chip, interpreted under ``HVD_PALLAS_INTERPRET=1``, else — and
for a shape ``kernel_plan`` refuses — the scan.
"""

import functools
from typing import NamedTuple

from ..common import metrics as _metrics

with _metrics.span("import:horovod_tpu.ops.kda"):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..common import logging as _log
    from ..common.compat import (
        pallas_tpu_compiler_params as _compiler_params)
    from . import pallas_attention as _pallas_attention
    from .pallas_attention import _mxu_dot

_LANES = 128
# Rows of a block of the [C, C] tiles (the module's docstring), and the
# largest exponent a decay inside one may take: sub * 5.
SUB = 16
_CAP = 80.0
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# Heads a grid step, the most first (``kernel_plan``).
_HEADS = (4, 2, 1)


def kda_chunked(q, k, v, g, beta, chunk: int = 64):
    """``o`` [b, T, H, V] in ``v``'s type of the recurrence above.

    q, k [b, T, H, K] (normed and scaled by the caller); v [b, T, H, V];
    g [b, T, H, K] float32, the log of the decay, in [-5, 0] (``_CAP /
    SUB``: the module's docstring); beta [b, T, H] float32. A length that
    ``chunk`` does not divide is padded with tokens that neither decay nor
    write (``g`` 0, ``beta`` 0), which no earlier token sees."""
    b, T, H, K = q.shape
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] *
                                    (x.ndim - 2)) for x in (q, k, v, g, beta))
    nc = (T + pad) // chunk
    # The sums inside a chunk are XLA's, as their transpose is.
    G = jnp.cumsum(g.astype(jnp.float32).reshape(b, nc, chunk, H, K),
                   axis=2).reshape(b, nc * chunk, H, K)
    use_pallas, interpret = _pallas_attention._resolve_dispatch(None)
    if not (use_pallas and kernel_plan(H, K, v.shape[-1], chunk, q.dtype)):
        interpret = None  # the scan
    return _kda(q, k, v, G, beta.astype(jnp.float32), chunk, interpret)[:, :T]


# ---------------------------------------------------------------------------
# One chunk of one head: 2-D arrays, matmuls, elementwise, static slices of
# rows. Traced by both drivers, so nothing here that Mosaic does not take.
# ---------------------------------------------------------------------------


def _hi_dot(a, b, contract=_NN):
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _tri(C, strict):
    rows = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return rows > cols if strict else rows >= cols


class _Blocks(NamedTuple):
    """The row blocks of a chunk's tiles: for block ``a`` the rows'
    ``exp(G_r - ref_a)`` [sub, K] and every column's ``exp(min(ref_a - G_j,
    _CAP))`` [C, K], ``ref_a`` the sum at the block's start."""
    rows: tuple
    into: tuple
    out_of: tuple


def _blocks(G, sub) -> _Blocks:
    C = G.shape[0]
    rows, into, out_of = [], [], []
    for a in range(C // sub):
        at = slice(a * sub, (a + 1) * sub)
        ref = G[a * sub - 1:a * sub] if a else jnp.zeros_like(G[:1])
        rows.append(at)
        into.append(jnp.exp(G[at] - ref))
        out_of.append(jnp.exp(jnp.minimum(ref - G, _CAP)))
    return _Blocks(tuple(rows), tuple(into), tuple(out_of))


def _scores(lefts, k, blocks: _Blocks, dt):
    """``M(X, K)`` [C, C] for each X of ``lefts``, unmasked, a block of
    rows at a time: the blocks' rows of every X stacked into one matmul."""
    sub = blocks.into[0].shape[0]
    out = [[] for _ in lefts]
    for at, into, out_of in zip(*blocks):
        stacked = jnp.concatenate([x[at] * into for x in lefts], 0)
        m = _mxu_dot(stacked.astype(dt), (k * out_of).astype(dt), _NT)
        for i, rows in enumerate(out):
            rows.append(m[i * sub:(i + 1) * sub])
    return [jnp.concatenate(rows, 0) for rows in out]


def _scores_bwd(d_lefts, lefts, k, blocks: _Blocks, dt):
    """The transpose of ``_scores`` under the masked cotangents ``d_lefts``
    [C, C] each: (each X's gradient [C, K], K's gradient as the tiles'
    columns, ``sum_r dM_rj X_r exp(G_r - G_j)`` summed over the tiles)."""
    sub = blocks.into[0].shape[0]
    d_rows = [[] for _ in lefts]
    d_cols = 0.0
    for at, into, out_of in zip(*blocks):
        d_stacked = jnp.concatenate([d[at] for d in d_lefts], 0).astype(dt)
        by_row = _mxu_dot(d_stacked, (k * out_of).astype(dt), _NN)
        for i, rows in enumerate(d_rows):
            rows.append(by_row[i * sub:(i + 1) * sub] * into)
        stacked = jnp.concatenate([x[at] * into for x in lefts], 0)
        d_cols = d_cols + _mxu_dot(d_stacked, stacked.astype(dt),
                                   _TN) * out_of
    return [jnp.concatenate(rows, 0) for rows in d_rows], d_cols


def _unit_lower_inverse(A, sub):
    """``(I + A)^-1`` of a strictly lower-triangular A [C, C], float32 at
    ``highest``, by the two nilpotent products of the module's
    docstring."""
    C = A.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (rows == cols).astype(jnp.float32)
    on_diagonal = jnp.where(rows // sub == cols // sub, A, 0.0)

    def inverse(n, order):
        """(I + n)^-1 = (I - n)(I + n^2)(I + n^4) ... for n^order = 0."""
        out, power, reach = eye - n, n, 2
        while reach < order:
            power = _hi_dot(power, power)
            out = out + _hi_dot(out, power)
            reach *= 2
        return out

    blocks = inverse(on_diagonal, sub)
    if C == sub:
        return blocks
    return _hi_dot(inverse(_hi_dot(blocks, A - on_diagonal), C // sub),
                   blocks)


def _chunk_parts(q, k, v, G, bcol, St0, sub):
    """What both passes form of a chunk: q, k [C, K], v [C, V] in their
    type, G [C, K] and bcol [C, 1] float32, St0 [V, K] float32 (the state
    transposed)."""
    dt, f32 = q.dtype, jnp.float32
    C = G.shape[0]
    qf, kf = q.astype(f32), k.astype(f32)
    decay = jnp.exp(G)
    to_end = jnp.exp(G[C - 1:] - G)
    blocks = _blocks(G, sub)
    m_qk, m_kk = _scores([qf, kf], kf, blocks, dt)
    kk = jnp.where(_tri(C, True), m_kk, 0.0)
    p = jnp.where(_tri(C, False), m_qk, 0.0)
    inv = _unit_lower_inverse(bcol * kk, sub)
    kg, qg, kd = kf * decay, qf * decay, kf * to_end
    state = St0.astype(dt)
    w = v.astype(f32) - _mxu_dot(kg.astype(dt), state, _NT)
    u = _hi_dot(inv, bcol * w)
    return dict(qf=qf, kf=kf, decay=decay, to_end=to_end, blocks=blocks,
                kk=kk, p=p, inv=inv, kg=kg, qg=qg, kd=kd, state=state, w=w,
                u=u)


def _chunk_forward(q, k, v, G, bcol, St0, sub):
    """(o [C, V] float32, the state after the chunk [V, K] float32)."""
    dt = q.dtype
    C = G.shape[0]
    c = _chunk_parts(q, k, v, G, bcol, St0, sub)
    u = c["u"].astype(dt)
    o = (_mxu_dot(c["qg"].astype(dt), c["state"], _NT)
         + _mxu_dot(c["p"].astype(dt), u, _NN))
    St1 = St0 * jnp.exp(G[C - 1:]) + _mxu_dot(u, c["kd"].astype(dt), _TN)
    return o, St1


def _chunk_backward(q, k, v, G, bcol, St0, do, dSt1, sub):
    """The transpose of ``_chunk_forward`` under ``do`` [C, V] and the
    later chunks' ``dSt1`` [V, K], both float32: (dq, dk [C, K], dv [C, V],
    dG [C, K], dbeta [C, 1], dSt0 [V, K]), float32."""
    dt = q.dtype
    C = G.shape[0]
    c = _chunk_parts(q, k, v, G, bcol, St0, sub)
    qf, kf, u, state = c["qf"], c["kf"], c["u"], c["state"]
    dot, dst = do.astype(dt), dSt1.astype(dt)
    ut, kd = u.astype(dt), c["kd"].astype(dt)
    last = jnp.exp(G[C - 1:])

    # O = Qg S0 + P U;  S1 = last o S0 + Kd^T U
    du = _mxu_dot(c["p"].astype(dt), dot, _TN) + _mxu_dot(kd, dst, _NT)
    dp = jnp.where(_tri(C, False), _mxu_dot(dot, ut, _NT), 0.0)
    dqg = _mxu_dot(dot, state, _NN)
    dkd = _mxu_dot(ut, dst, _NN)
    dSt0 = _mxu_dot(dot, c["qg"].astype(dt), _TN) + dSt1 * last
    d_last = jnp.sum(St0 * dSt1, axis=0, keepdims=True) * last
    # U = (I + A)^-1 (beta o W);  W = V - Kg S0;  A = beta o KK
    dr = _hi_dot(c["inv"], du, _TN)
    da = -jnp.where(_tri(C, True), _hi_dot(dr, u, _NT), 0.0)
    dw = bcol * dr
    dbeta = (jnp.sum(dr * c["w"], axis=1, keepdims=True)
             + jnp.sum(da * c["kk"], axis=1, keepdims=True))
    dwt = dw.astype(dt)
    dkg = -_mxu_dot(dwt, state, _NN)
    dSt0 = dSt0 - _mxu_dot(dwt, c["kg"].astype(dt), _TN)
    # The two tiles, by their rows and by their columns.
    (dq_m, dk_rows), dk_cols = _scores_bwd([dp, bcol * da], [qf, kf], kf,
                                           c["blocks"], dt)
    dq = dq_m + dqg * c["decay"]
    dk = dk_rows + dk_cols + dkg * c["decay"] + dkd * c["to_end"]
    by_end = dkd * c["kd"]
    dG = (qf * dq_m + kf * dk_rows - kf * dk_cols + dqg * c["qg"]
          + dkg * c["kg"] - by_end)
    at_end = jnp.sum(by_end, axis=0, keepdims=True) + d_last
    is_last = lax.broadcasted_iota(jnp.int32, dG.shape, 0) == C - 1
    dG = dG + jnp.where(is_last, at_end, 0.0)
    return dq, dk, dw, dG, dbeta, dSt0


# ---------------------------------------------------------------------------
# The plan and the two drivers.
# ---------------------------------------------------------------------------


class ScanPlan(NamedTuple):
    """What the ``pallas_call`` of a pass does, all of it static."""
    chunk: int       # tokens a grid step
    sub: int         # rows of a block of the [chunk, chunk] tiles
    heads: int       # heads a grid step, each with its own chunk
    vmem_bytes: int  # counted VMEM, of all the step's heads


def kernel_plan(H, K, V, chunk, dtype, *, kind="bwd"):
    """The grid step of pass ``kind`` ("fwd" or "bwd") for heads of ``K``
    key and ``V`` value channels and chunks of ``chunk`` tokens in
    ``dtype``: a pure function of the shape. None where the kernels do not
    take the shape and the scan does: channels off the lane grid (128), or
    a chunk that blocks of ``SUB`` rows do not divide or that is neither
    half a lane tile nor whole ones.

    A grid step carries ``heads`` heads, the most of ``_HEADS`` that
    divides ``H`` and whose counted VMEM, a head's times ``heads``, fits
    the budget (why several: the module's docstring)."""
    if K % _LANES or V % _LANES or chunk % SUB:
        return None
    if chunk != _LANES // 2 and chunk % _LANES:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    wide = chunk * (K + V) * (itemsize + 4)           # q|k, v, G, beta
    if kind == "bwd":
        wide = 2 * wide + chunk * V * 4                # their gradients, do
    state = V * K * 4
    # Pipelined blocks twice, the carried state, and the float32 values a
    # chunk forms: a dozen [chunk, K], the blocks' decays, six tiles; the
    # backward pass twice as many, and float32 operands twice as many
    # again (the parts of their products at ``highest``). Held to what
    # Mosaic allocates for a described v5e (PERF.md, PR 48).
    values = ((12 + chunk // SUB) * chunk * max(K, V) * 4
              + 6 * chunk * chunk * 4)
    values *= (2 if kind == "bwd" else 1) * (itemsize // 2)
    vmem = 2 * (wide + state) + state + values
    for heads in _HEADS:
        if H % heads == 0 and heads * vmem <= _pallas_attention.VMEM_BUDGET:
            return ScanPlan(chunk, SUB, heads, heads * vmem)
    return None


def _log_plan(kind, shape, dtype, plan):
    """Everything a plan decides is static, so it is logged once, when the
    call is traced (``HOROVOD_LOG_LEVEL=debug``), and counted: the host
    traces this ``pallas_call`` and lowers it to Mosaic."""
    _metrics.inc(f"kernels.traced.kda_{kind}")
    _metrics.inc(f"kernels.kda_{kind}.heads_per_step", plan.heads)
    _log.debug(
        f"kda_{kind} {tuple(shape)} {jnp.dtype(dtype).name}: chunks of "
        f"{plan.chunk} tokens, {plan.heads} heads a step, blocks of "
        f"{plan.sub} rows, VMEM {plan.vmem_bytes} B")


def _heads_of(ref, heads, width=None):
    """[heads, C, width] of a block [C, heads * K]: each head's lanes, or
    the first ``width`` of them."""
    K = ref.shape[-1] // heads
    return jnp.stack([ref[:, i * K:i * K + (width or K)]
                      for i in range(heads)])


def _put_heads(ref, x):
    """``_heads_of`` back: x [heads, C, K] into the block's lanes."""
    K = x.shape[-1]
    for i in range(x.shape[0]):
        ref[:, i * K:(i + 1) * K] = x[i].astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, sub, heads):
    """One chunk of each of the step's heads, ``_chunk_forward`` over a
    leading head axis: every matmul of it is one batched ``dot_general``,
    which Mosaic unrolls a head after a head, so a link of one head's chain
    stands beside the same link of the others (traced a head at a time the
    chains are scheduled one after another: PERF.md, PR 48). The states
    ride the chunks in scratch, and with a ``states`` output each chunk
    leaves the one it started from."""
    s_ref, st_ref = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, jnp.float32)

    St0 = st_ref[...]
    if s_ref is not None:
        s_ref[...] = St0
    o, St1 = jax.vmap(lambda *x: _chunk_forward(*x, sub))(
        *(_heads_of(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref)),
        _heads_of(b_ref, heads, 1), St0)
    _put_heads(o_ref, o)
    st_ref[...] = St1


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dst_ref, *, sub, heads):
    """One chunk of each of the step's heads as in ``_fwd_kernel``, the
    chunks from the last to the first (the index maps turn them round);
    the states' gradients ride in scratch."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, jnp.float32)

    dq, dk, dv, dG, dbeta, dSt0 = jax.vmap(
        lambda *x: _chunk_backward(*x, sub))(
        *(_heads_of(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref)),
        _heads_of(b_ref, heads, 1), s_ref[...],
        _heads_of(do_ref, heads).astype(jnp.float32), dst_ref[...])
    _put_heads(dq_ref, dq)
    _put_heads(dk_ref, dk)
    _put_heads(dv_ref, dv)
    _put_heads(dg_ref, dG)
    _put_heads(db_ref, jnp.broadcast_to(dbeta, dG.shape))
    dst_ref[...] = dSt0


def _kda_call(kind, kernel, plan, grid, in_specs, out_specs, out_shape,
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
        name=f"kda_{kind}",
    )


def _specs(plan, K, V, order):
    """Block specs over the grid (b, heads of a step, chunk) of the
    operands as they lie, [b, T, H K] and [b, T, H V], where a step's heads
    are neighbouring lane tiles, and of the states [b, H, nc, V, K];
    ``order`` turns a grid step into its chunk."""
    C, n = plan.chunk, plan.heads
    return {
        "k": pl.BlockSpec((None, C, n * K), lambda b, h, c: (b, order(c), h)),
        "v": pl.BlockSpec((None, C, n * V), lambda b, h, c: (b, order(c), h)),
        "state": pl.BlockSpec((None, n, None, V, K),
                              lambda b, h, c: (b, h, order(c), 0, 0)),
    }


def _merged(x):
    return x.reshape(x.shape[:2] + (-1,))


def _operands(q, k, v, G, beta):
    """The forward's operands as the kernels read them: heads merged into
    the lanes, beta [b, T, H] the same value in every lane of its head."""
    return (_merged(q), _merged(k), _merged(v), _merged(G),
            _merged(jnp.broadcast_to(beta[..., None], G.shape)))


def _pallas_forward(q, k, v, G, beta, chunk, interpret, states):
    b, T, H, K = q.shape
    V, nc = v.shape[-1], T // chunk
    plan = kernel_plan(H, K, V, chunk, q.dtype, kind="fwd")
    _log_plan("fwd", q.shape, q.dtype, plan)
    specs = _specs(plan, K, V, lambda c: c)
    out_specs, out_shape = [specs["v"]], [
        jax.ShapeDtypeStruct((b, T, H * V), v.dtype)]
    if states:
        out_specs.append(specs["state"])
        out_shape.append(jax.ShapeDtypeStruct((b, H, nc, V, K), jnp.float32))
    out = _kda_call(
        "fwd", functools.partial(_fwd_kernel, sub=plan.sub,
                                 heads=plan.heads), plan,
        (b, H // plan.heads, nc), [specs[x] for x in "kkvkk"], out_specs,
        out_shape, [pltpu.VMEM((plan.heads, V, K), jnp.float32)], interpret,
    )(*_operands(q, k, v, G, beta))
    return out[0].reshape(b, T, H, V), (out[1] if states else None)


def _pallas_backward(q, k, v, G, beta, S, do, chunk, interpret):
    b, T, H, K = q.shape
    V, nc, f32 = v.shape[-1], T // chunk, jnp.float32
    plan = kernel_plan(H, K, V, chunk, q.dtype, kind="bwd")
    _log_plan("bwd", q.shape, q.dtype, plan)
    specs = _specs(plan, K, V, lambda c: nc - 1 - c)
    wide = jax.ShapeDtypeStruct((b, T, H * K), f32)
    dq, dk, dv, dG, dbeta = _kda_call(
        "bwd", functools.partial(_bwd_kernel, sub=plan.sub,
                                 heads=plan.heads), plan,
        (b, H // plan.heads, nc),
        [specs[x] for x in ("k", "k", "v", "k", "k", "state", "v")],
        [specs[x] for x in "kkvkk"],
        [jax.ShapeDtypeStruct((b, T, H * K), q.dtype),
         jax.ShapeDtypeStruct((b, T, H * K), k.dtype),
         jax.ShapeDtypeStruct((b, T, H * V), v.dtype), wide, wide],
        [pltpu.VMEM((plan.heads, V, K), f32)], interpret,
    )(*_operands(q, k, v, G, beta), S, _merged(do))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dG.reshape(G.shape), dbeta.reshape(G.shape)[..., 0])


def _by_chunks(x, chunk):
    """[b, T, H, ...] -> [b, H, nc, chunk, ...]."""
    b, T, H = x.shape[:3]
    x = x.reshape((b, T // chunk, chunk, H) + x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def _by_tokens(x):
    """``_by_chunks`` back."""
    x = jnp.moveaxis(x, 1, 3)
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def _scan_forward(q, k, v, G, beta, chunk):
    """(o [b, T, H, V] in v's type, the state each chunk started from
    [b, H, nc, V, K] float32), a ``lax.scan`` over the chunks a head."""
    K, V = q.shape[-1], v.shape[-1]

    def head(*xs):
        def step(St0, x):
            o, St1 = _chunk_forward(*x, St0, SUB)
            return St1, (o.astype(v.dtype), St0)

        return lax.scan(step, jnp.zeros((V, K), jnp.float32), xs)[1]

    o, S = jax.vmap(jax.vmap(head))(*(
        _by_chunks(x, chunk) for x in (q, k, v, G, beta[..., None])))
    return _by_tokens(o), S


def _scan_backward(q, k, v, G, beta, S, do, chunk):
    K, V = q.shape[-1], v.shape[-1]

    def head(*xs):
        def step(dSt1, x):
            *x, St0, do = x
            *grads, dSt0 = _chunk_backward(*x, St0, do.astype(jnp.float32),
                                           dSt1, SUB)
            return dSt0, grads

        return lax.scan(step, jnp.zeros((V, K), jnp.float32), xs,
                        reverse=True)[1]

    by_chunks = [_by_chunks(x, chunk) for x in (q, k, v, G, beta[..., None])]
    dq, dk, dv, dG, dbeta = jax.vmap(jax.vmap(head))(
        *by_chunks, S, _by_chunks(do, chunk))
    return (_by_tokens(dq).astype(q.dtype), _by_tokens(dk).astype(k.dtype),
            _by_tokens(dv).astype(v.dtype), _by_tokens(dG),
            _by_tokens(dbeta)[..., 0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, G, beta, chunk, interpret):
    """``kda_chunked`` on whole chunks with ``G`` the sums inside each;
    ``interpret`` None: the scan, else the kernels."""
    if interpret is None:
        return _scan_forward(q, k, v, G, beta, chunk)[0]
    return _pallas_forward(q, k, v, G, beta, chunk, interpret, False)[0]


def _kda_fwd(q, k, v, G, beta, chunk, interpret):
    if interpret is None:
        o, S = _scan_forward(q, k, v, G, beta, chunk)
    else:
        o, S = _pallas_forward(q, k, v, G, beta, chunk, interpret, True)
    return o, (q, k, v, G, beta, S)


def _kda_bwd(chunk, interpret, residuals, do):
    if interpret is None:
        return _scan_backward(*residuals, do, chunk)
    return _pallas_backward(*residuals, do, chunk, interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)
