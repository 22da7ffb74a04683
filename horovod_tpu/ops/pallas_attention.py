"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer stack, written for the hardware: a grid
step holds several heads and a resident chunk of the sequence on both
sides in VMEM and walks the chunk's sub-tiles itself. The S = QK^T and
P.V matmuls run on the MXU with fp32 accumulation, the online-softmax
state (running max / normalizer / accumulator) lives in VMEM scratch,
and the full attention matrix never materializes (the same
streaming-accumulation math as ``parallel.ring_attention``).

What a kernel's time goes with is the tiles it walks and the latency of
each tile's chain of phases (PERF.md section 5), so the walk visits only
the sub-tiles the causal structure and the window leave: the loop's
bounds come from them, tiles above the diagonal or beyond the window are
never entered. Every visited tile takes the mask, made once a tile for
all heads: an unmasked second body for tiles wholly under the diagonal
saved 0-3 % of a kernel and doubled what every start pays to trace and
lower it (PERF.md section 6, PR 27). ``kernel_plan`` decides heads a
step, chunk and sub-tile from the shape alone; nothing else does.

Scope: forward AND backward. Training's forward emits the per-row
log-sum-exp alongside O; the backward is one Pallas kernel, ``flash_bwd``,
that walks the visited tiles once and makes dQ, dK and dV from one S, one
P = exp(S - lse) and one dP = dO.V^T a tile: five matmuls and one ``exp``,
the attention matrix never in HBM. dK and dV accumulate in float32 VMEM
for a K/V head's whole sequence, dQ for the resident chunk of Q across
the K chunks. Where a sequence is too long for those accumulators
(``kernel_plan(kind="bwd")`` is None: T 65,536 at D 128) the backward is
the standard two passes instead, ``flash_dq`` accumulating dQ across K
tiles and ``flash_dkv`` accumulating dK/dV across Q tiles, each rebuilding
the tile for itself (seven matmuls, two ``exp``); all three share the
tile's body (``_p_ds``) and the dK/dV walk is one function.
``flash_attention_block_grads`` exposes the same per-block backward for
ring attention's backward ring pass (``parallel.ring_attention``); it and
``flash_attention_block`` merge the heads, call their ``_merged`` forms
and split the heads again, and a caller that hands several calls one
merged array calls those forms itself (``ops/eva_attention.py``).

Head counts: the Q side (q, o, dO, dQ, lse, delta, the Q segment ids) is
merged as ``[B*H, T, D]`` and the K side (k, v, dK, dV, the K segment
ids) as ``[B*Hkv, T, D]``, Hkv a divisor of H; the group ``g = H / Hkv``
is read from the two shapes and nothing else. Query head j reads K/V head
j // g through the K side's index map, and the backward, whose grid is
over K/V heads, sums a group's query heads into the float32 accumulators
it holds in VMEM. So K and V are never repeated to H heads and no
gradient of theirs is written at H heads. With g = 1 every plan, grid,
index map and kernel body is the multi-head one. The XLA twins take the
same operands and repeat K and V inside themselves.

A third mask rule beside the diagonal and the window is the block-causal
one (``BlockCausal``, the public ``blocks=(q_block, k_block)``): key ``j``
is visible to query ``i`` iff ``(k_off + j) // k_block < (q_off + i) //
q_block``, the two sides counted in blocks of their own sizes, a query's
own block and every later one hidden. It rides where the window does, so
the bounds, the mask and the XLA twins are the only places that know it:
the walk enters the tiles some query of the tile may see and no other.
The EVA mixer's chunk summaries are attended under it
(``ops/eva_attention.py``).

Block offsets ride in as prefetched scalars and enter the walk's bounds,
so the same kernel serves ring attention's rotating K/V blocks (global
causal masking between sequence blocks) and the plain single-block case.
On TPU the kernels compile through Mosaic; tests interpret them on CPU
(``_resolve_dispatch``).

Each ``pallas_call`` carries a ``name`` (``flash_fwd``, ``flash_bwd``;
``flash_dq``, ``flash_dkv``), which jax also writes as a scope into the custom call's
``op_name``; the XLA twins carry the scope ``flash_xla``. A device trace
tells the kernels apart, and a fall-back from them, by these names
(docs/diagnostics.md, "Tracing").
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import logging as _log
from ..common import metrics as _metrics
from ..common.compat import pallas_tpu_compiler_params as _compiler_params

NEG_INF = -1e30

# What ``kernel_plan`` works from; docs/diagnostics.md ("Tracing") has its
# decisions at the benchmark's shapes and PERF.md (section 6, PR 27) the
# chip sweep behind each number.
# The [tq, tk] score tile one loop iteration computes, both ways. A tile's
# chain (matmul, row max, exp, row sum, matmul) runs one phase at a time,
# so its fixed latencies are paid a tile: 256 x 256 tiles visit a sixth
# fewer elements at T 1024 and take a third longer.
_TILE_CAP = 512
# A grid step takes heads until it holds this many score elements (2 at
# T 1024, 16 at T 128), so that its fixed cost (0.35-0.5 us of DMA issue
# and pipeline bookkeeping) is buried.
_STEP_ELEMS = 1 << 21
_MAX_HEADS = 16
# Heads that share one loop body, so that one's matmul runs under
# another's ``exp``: until the body holds this many score elements, at
# most four (4 heads of 128 x 128, 2 of 512 x 512). Every head of a body
# is traced and lowered to Mosaic on the host at every start, compile
# cache or not: at T 128 four add 0.95 s to a 21 s set-up, and eight
# would add about 1.9 s (over set-up's bound of 10 %) for 35 us a
# forward call (PERF.md section 6, PR 27).
_BODY_ELEMS = 1 << 19
_MAX_UNROLL = 4
# The longest resident chunk (``_pick_block``'s largest tile): nothing
# longer fits ``VMEM_BUDGET`` at any head width.
_CHUNK_CAP = 8192
# Lanes the forward's running max and sum occupy in scratch, the same
# value in each: a [tq, 1] float32 takes 128 lanes of VMEM either way,
# and kept one lane wide its stores are masked and every use a lane
# broadcast (the forward took 652 us a call at T 1024 so, 473 this way).
_STAT_LANES = 128
# VMEM a plan may count: every pipelined block twice, the scratch, and
# one sub-tile's float32 temporaries. Row vectors ([T, 1] float32: lse,
# delta, m, l, segment ids) occupy T x 128 lanes there.
VMEM_BUDGET = 40 << 20
# The fused backward's: it also holds a K/V head's dK and dV for the whole
# sequence, in float32 and as the blocks they leave in (T x D x 16 bytes
# in bf16: 32 MiB at T 8,192 and D 256), and a chunk halved costs it 4 %
# (PERF.md section 6, PR 37). Mosaic took a limit of 115 MiB of the
# chip's 128 on the kernel alone; this leaves a step's other programs 40.
BWD_VMEM_BUDGET = 80 << 20
_VMEM_DEFAULT_LIMIT = 16 << 20


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


def merge_state(m, l, acc, m_b, l_b, acc_b):
    """The online-softmax combine of a running state with one more key
    set's (``flash_attention_block``'s): m, l [B, H, T] and acc [B, T, H,
    D], float32; returns the merged (m, l, acc). A row that neither set
    has a key for stays empty (m = NEG_INF, l = 0). Ring attention joins
    its K/V blocks with it, EVA attention its two key sets."""
    m_new = jnp.maximum(m, m_b)
    alive = m_new > NEG_INF / 2
    c_old = jnp.where(alive, jnp.exp(m - m_new), 1.0)
    c_blk = jnp.where(alive & (m_b > NEG_INF / 2),
                      jnp.exp(m_b - m_new), 0.0)
    l = l * c_old + l_b * c_blk
    acc = (acc * c_old.transpose(0, 2, 1)[..., None] +
           acc_b * c_blk.transpose(0, 2, 1)[..., None])
    return m_new, l, acc


# ---------------------------------------------------------------------------
# The plan: heads a grid step, resident chunk, sub-tile, and the walk.
# ---------------------------------------------------------------------------


class KernelPlan(NamedTuple):
    """What one ``pallas_call`` of a pass does, all of it static."""
    heads: int           # rows of the merged [B*H, T, D] Q side a grid step
    chunk_q: int         # resident rows of Q (and dO, lse, delta, O)
    chunk_k: int         # resident rows of K and V
    tile_q: int          # the in-kernel loop's sub-tile, [tile_q, tile_k]
    tile_k: int
    unroll: int          # heads that share one loop body
    grid: tuple          # (head blocks, outer chunks, inner chunks); "bwd":
    #                      (K/V head blocks, passes, Q chunks, K chunks)
    tiles_visited: int   # sub-tiles a head's walk enters (q_off == k_off)
    vmem_bytes: int      # counted VMEM; ``vmem_limit_bytes`` is set from it
    group: int = 1       # query heads that share one K/V head (H / Hkv)

    @property
    def kv_heads(self) -> int:
        """Rows of the merged [B*Hkv, T, D] K side a grid step holds: the
        step's query heads lie in one group or cover whole groups."""
        return max(1, self.heads // self.group)

    @property
    def shared(self) -> int:
        """Query heads of a grid step that read one K/V head."""
        return self.heads // self.kv_heads

    @property
    def passes(self) -> int:
        """Grid steps that a group's query heads take: the forward and the
        dQ pass send ``passes`` consecutive head blocks to one K/V block,
        the backward and the dK/dV pass visit them in turn on a
        sequential dimension."""
        return self.group // self.shared


def _is_static(*xs):
    return all(isinstance(x, int) for x in xs)


def _imin(a, b):
    return min(a, b) if _is_static(a, b) else jnp.minimum(a, b)


def _imax(a, b):
    return max(a, b) if _is_static(a, b) else jnp.maximum(a, b)


def _clip_div(x, t, n):
    """``floor(x / t)`` clamped into [0, n], on Python ints (the plan's
    counts) and on the kernel's int32 scalars alike."""
    if _is_static(x):
        return min(max(x, 0) // t, n)
    return jnp.minimum(jax.lax.div(jnp.maximum(x, 0), t), n)


class BlockCausal(NamedTuple):
    """The block-causal rule, carried where a window is: global row ``i``
    sees global column ``j`` iff ``j // k_block < i // q_block``."""
    q_block: int
    k_block: int


def _div(x, t):
    """``floor(x / t)`` of a non-negative Python int or int32 value."""
    return x // t if _is_static(x) else jax.lax.div(x, jnp.int32(t))


def _block_visible(rows, cols, rule: BlockCausal):
    """``BlockCausal``'s mask of global ``rows`` [tq, 1] against global
    ``cols`` [1, tk] (non-negative int32): the single definition, the
    kernels' and the twins'."""
    return _div(cols, rule.k_block) < _div(rows, rule.q_block)


def _k_bounds(q_lo, tq, k_base, tk, n, causal, window):
    """The K sub-tiles (of ``n``, ``tk`` wide, the first at global column
    ``k_base``) that the Q sub-tile of global rows ``q_lo .. q_lo+tq-1``
    walks, as ``(first, end)``: tiles wholly above the diagonal or beyond
    the window lie outside."""
    if not causal:
        return 0, n
    if isinstance(window, BlockCausal):
        # Columns under the tile's last row's block: j < limit.
        limit = _div(q_lo + tq - 1, window.q_block) * window.k_block
        return 0, _clip_div(limit - k_base + tk - 1, tk, n)
    end = _clip_div(q_lo + tq - 1 - k_base + tk, tk, n)   # starting <= q_hi
    if window is None:
        return 0, end
    # Visible: k_hi >= q_lo - window + 1.
    return _imin(_clip_div(q_lo - window + 1 - k_base, tk, n), end), end


def _q_bounds(k_lo, tk, q_base, tq, n, causal, window):
    """The transposed walk of the backward: the Q sub-tiles (of ``n``,
    ``tq`` tall, the first at global row ``q_base``) that see the K
    sub-tile of global columns ``k_lo .. k_lo+tk-1``."""
    if not causal:
        return 0, n
    if isinstance(window, BlockCausal):
        # Rows past the block of the tile's first column: i >= start.
        start = (_div(k_lo, window.k_block) + 1) * window.q_block
        return _clip_div(start - q_base, tq, n), n
    first = _clip_div(k_lo - q_base, tq, n)        # first with q_hi >= k_lo
    if window is None:
        return first, n
    # Visible: q_lo - k_hi < window.
    return first, _imax(
        _clip_div(k_lo + tk - 1 + window - q_base + tq - 1, tq, n), first)


def _pick_block(t: int, cap: int) -> Optional[int]:
    """Largest MXU-friendly tile (multiple of the fp32 sublane count, up
    to ``cap``) that divides ``t``; None when ``t`` isn't tileable
    (callers fall back to the XLA path rather than reason about
    padded-position masking)."""
    for c in (8192, 4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if c <= cap and t % c == 0:
            return c
    return None


def _chunk(t: int, cap: int) -> int:
    """The resident chunk of a sequence of ``t``: all of it where that is
    at most ``cap``, else the largest power of two up to ``cap`` that
    divides it."""
    return t if t <= cap else (_pick_block(t, cap) or t)


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


def _vmem_bytes(kind, heads, kv_heads, unroll, cq, ck, tq, tk, d, itemsize,
                out_itemsize, segments, state, t_k):
    """VMEM one grid step of pass ``kind`` holds, counted as Mosaic lays
    it out: the last dimension padded to 128 lanes (a [T, 1] float32 row
    vector is T x 512 bytes), pipelined blocks double-buffered, and six
    float32 [tq, tk] temporaries for every head of a loop body. The K
    side (k, v, dk, dv and their accumulators) holds ``kv_heads``; the
    fused backward's dk and dv, blocks and accumulators, hold a K/V
    head's whole sequence of ``t_k``."""
    dp = _lanes(d)
    row = 128 * 4
    q_side = heads * cq * dp
    k_side = kv_heads * ck * dp
    blocks = (q_side + 2 * k_side) * itemsize              # q, k, v
    if segments:
        blocks += (heads * cq + kv_heads * ck) * row
    if kind == "fwd":
        blocks += q_side * out_itemsize                    # o (acc)
        blocks += heads * cq * row * (2 if state else 1)   # lse | m, l
        scratch = q_side * 4 + 2 * heads * cq * row        # acc, m, l
    else:
        blocks += q_side * itemsize + 2 * heads * cq * row  # do, lse, delta
        scratch = 0
        if kind != "dkv":                                  # dq and its acc
            blocks += q_side * out_itemsize
            scratch += q_side * 4
        if kind != "dq":                                   # dk, dv, accs
            held = kv_heads * (t_k if kind == "bwd" else ck) * dp
            blocks += 2 * held * out_itemsize
            scratch += 2 * held * 4
    return 2 * blocks + scratch + 6 * unroll * tq * tk * 4


def kernel_plan(BH, Tq, Tk, D, dtype, causal, window=None, *,
                segments=False, kind="fwd", out_dtype=None, state=False,
                group=1):
    """The grid step of pass ``kind`` ("fwd", "bwd", "dq" or "dkv") for
    merged ``[BH, Tq, D]`` Q-side operands of ``dtype`` over ``[BH /
    group, Tk, D]`` K-side ones: a pure function of the shape. None where
    the kernels do not take the shape and the XLA twins do: a sequence no
    tile divides, or a head so wide that no chunk fits the budget. For
    "bwd", None also where a K/V head's whole-sequence accumulators do
    not fit ``BWD_VMEM_BUDGET`` at any chunk: the two passes "dq" and
    "dkv" run then.

    Heads a step: enough that a step holds ``_STEP_ELEMS`` score elements
    (16 at T 128, 2 at T 1024, 1 from T 2048 on), a divisor of ``BH``
    that lies in one group of ``group`` query heads or covers whole
    groups, fewer where that many do not fit;
    of them ``_BODY_ELEMS`` score elements' worth, at most
    ``_MAX_UNROLL``, share a loop body. Chunk: the whole sequence on both
    sides while the counted VMEM stays under ``VMEM_BUDGET``, else the
    largest power-of-two chunk that does — the chunks are then grid
    dimensions, the inner one sequential. Sub-tile:
    the largest divisor of the chunk up to ``_TILE_CAP``. The count is
    that of a block on the diagonal (``q_off == k_off``), per head.

    The K side has ``BH / group`` heads. The forward and the dQ pass keep
    the grid of the Q side and send a step's heads to their K/V block
    through the index map. The dK/dV pass has a grid over K/V heads and
    sums a group into the one accumulator pair a K/V head has: as heads
    of one step where the step covers whole groups, else as
    ``plan.passes`` times the Q chunks on the sequential dimension.

    The fused backward is the dK/dV pass's walk with dQ made beside: grid
    (K/V head blocks, ``plan.passes``, Q chunks, K chunks), the last three
    sequential. dK and dV accumulate for the K/V head's whole sequence
    under everything its group sends, and leave in one block when the
    head is done; dQ accumulates for a head block's resident chunk across
    the K chunks, the innermost dimension, in ascending order."""
    if _pick_block(Tq, 8) is None or _pick_block(Tk, 8) is None:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype or (jnp.float32 if state
                                           else dtype)).itemsize
    budget = BWD_VMEM_BUDGET if kind == "bwd" else VMEM_BUDGET
    cap = min(max(Tq, Tk), _CHUNK_CAP)
    while True:
        cq, ck = _chunk(Tq, cap), _chunk(Tk, cap)
        tq, tk = _pick_block(cq, _TILE_CAP), _pick_block(ck, _TILE_CAP)
        body = max(1, min(_MAX_UNROLL, _BODY_ELEMS // (tq * tk)))
        want = max(1, min(_MAX_HEADS, _STEP_ELEMS // (cq * ck)))
        fits = None
        # The most heads, up to the wanted, that divide BH and fit.
        for heads in range(want, 0, -1):
            if BH % heads or (group % heads and heads % group):
                continue
            unroll = max(u for u in range(1, body + 1) if heads % u == 0)
            vmem = _vmem_bytes(kind, heads, max(1, heads // group), unroll,
                               cq, ck, tq, tk, D, itemsize, out_itemsize,
                               segments, state, Tk)
            if vmem <= budget:
                fits = heads, unroll, vmem
                break
        if fits or cap <= max(_TILE_CAP, 128):
            break
        cap = max(c for c in (1 << s for s in range(3, 24)) if c < cap)
    if fits is None:
        return None
    heads, unroll, vmem = fits
    n_qc, n_kc = Tq // cq, Tk // ck
    visited = 0
    for qc in range(n_qc):
        for kc in range(n_kc):
            if kind in ("dkv", "bwd"):
                walks = (_q_bounds(kc * ck + j * tk, tk, qc * cq, tq,
                                   cq // tq, causal, window)
                         for j in range(ck // tk))
            else:
                walks = (_k_bounds(qc * cq + i * tq, tq, kc * ck, tk,
                                   ck // tk, causal, window)
                         for i in range(cq // tq))
            visited += sum(end - first for first, end in walks)
    plan = KernelPlan(heads, cq, ck, tq, tk, unroll, (), visited, vmem,
                      group)
    kv_blocks = BH // heads // plan.passes
    grid = {"dkv": (kv_blocks, n_kc, plan.passes * n_qc),
            "bwd": (kv_blocks, plan.passes, n_qc, n_kc)}.get(
                kind, (BH // heads, n_qc, n_kc))
    return plan._replace(grid=grid)


def _kernels_take(kinds, q, k, causal, window, segments, **out) -> bool:
    """Whether ``kernel_plan`` has a grid step for every pass of ``kinds``
    on merged ``q`` and ``k``; where it has not, the callers take the XLA
    twins."""
    BH, Tq, D = q.shape
    return all(
        kernel_plan(BH, Tq, k.shape[1], D, q.dtype, causal, window,
                    segments=segments, kind=kind, group=BH // k.shape[0],
                    **out) is not None
        for kind in kinds)


def _log_plan(kind, shape, dtype, causal, window, plan):
    """Everything a plan decides is static, so it is logged once, when
    the call is traced (``HOROVOD_LOG_LEVEL=debug``), and counted: the
    host traces this ``pallas_call`` and lowers it to Mosaic. A call
    whose K side has fewer heads than its Q side counts a second time,
    under ``kernels.grouped.``, and one under the block-causal rule under
    ``kernels.blockcausal.``."""
    _metrics.inc(f"kernels.traced.flash_{kind}")
    if plan.group > 1:
        _metrics.inc(f"kernels.grouped.flash_{kind}")
    if isinstance(window, BlockCausal):
        _metrics.inc(f"kernels.blockcausal.flash_{kind}")
    _log.debug(
        f"flash_{kind} {tuple(shape)} {jnp.dtype(dtype).name} "
        f"causal={causal} window={window}: {plan.heads} heads a step "
        f"({plan.unroll} a loop body) over {plan.kv_heads} K/V "
        f"(group {plan.group}), chunk "
        f"{plan.chunk_q}x{plan.chunk_k}, sub-tile "
        f"{plan.tile_q}x{plan.tile_k}, grid {plan.grid}, "
        f"{plan.tiles_visited} tiles a head, VMEM {plan.vmem_bytes} B")


# ---------------------------------------------------------------------------
# The kernels: one body a pass.
# ---------------------------------------------------------------------------


def _for_each(n, fn):
    """``fn(i)`` for i in [0, n): inline where n is 1, else an in-kernel
    loop (never a Python unroll over tiles: compile time)."""
    if n == 1:
        fn(0)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (fn(i), c)[1], None)


def _for_heads(n, compute, commit=None, unroll=1):
    """The heads of a grid step are the innermost loop: every head walks
    the same tiles, so the bounds and the mask are made once a tile.
    ``unroll`` heads share a loop body (unrolled by hand: Mosaic takes a
    loop whole or not at all), and every head of a body computes before
    any ``commit``s its result to the scratch, so that no store of one
    head stands between another's loads and the scheduler can run one
    head's matmul under another's ``exp``."""
    def group(i):
        heads = [i * unroll + r for r in range(unroll)]
        results = [compute(g) for g in heads]
        if commit is not None:
            for g, result in zip(heads, results):
                commit(g, result)

    _for_each(n // unroll, group)


def _when(cond, fn):
    """``fn()`` where ``cond`` holds: a Python bool decides at trace
    time, a traced one predicates."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _ends(i, n):
    """Whether step ``i`` of a sequential grid dimension of ``n`` is its
    (first, last): True of both where the dimension is one step."""
    return (True, True) if n == 1 else (i == 0, i == n - 1)


def _both(a, b):
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _walk(bounds, tile, ends, init, finish):
    """Run ``tile(index)`` over the walk's ``(first, end)``, between
    ``init`` in the first grid step of what the accumulators live across
    and ``finish`` in the last; ``ends`` says whether this step is
    either."""
    _when(ends[0], init)
    jax.lax.fori_loop(*bounds, lambda t, c: (tile(t), c)[1], None)
    _when(ends[1], finish)


def _rows(i, t):
    return pl.ds(i * t if isinstance(i, int) else pl.multiple_of(i * t, t),
                 t)


def _tile_mask(q_lo, k_lo, tq, tk, causal, window):
    """Which elements of the [tq, tk] tile at global (q_lo, k_lo) the
    causal structure and the window keep (None: all). One per tile, for
    all heads of a loop body."""
    if not causal:
        return None
    if isinstance(window, BlockCausal):
        return _block_visible(
            q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0),
            k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1), window)
    rel = (q_lo - k_lo) + (jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) -
                           jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1))
    keep = rel >= 0
    if window is not None:
        keep = jnp.logical_and(keep, rel < window)
    return keep


def _across(x, n):
    """A row statistic, kept ``_STAT_LANES`` lanes wide with the same
    value in every lane, against an ``n``-lane operand: no lane broadcast
    where ``n`` is a multiple of the width."""
    w = x.shape[-1]
    if n <= w:
        return x[:, :n]
    if n % w:
        return x[:, :1]
    return pltpu.repeat(x, n // w, axis=1)


def _keep(mask, qs_ref, ks_ref, g, c, rows, cols):
    """The tile's mask composed with the segment ids of head ``g`` and its
    K/V head ``c`` (either may be absent; None means every element is
    kept)."""
    if qs_ref is None:
        return mask
    same = qs_ref[g, rows, :] == ks_ref[c, cols, :].reshape(1, -1)
    return same if mask is None else jnp.logical_and(mask, same)


def _kv_head(plan):
    """Head ``g`` of a grid step -> the row of the step's K-side blocks
    it reads: ``g`` itself without a group (and in a step of one head,
    whatever the group: the index map has chosen the row)."""
    n = plan.shared
    if n == 1:
        return lambda g: g
    if plan.kv_heads == 1:
        return lambda g: 0
    return lambda g: g // n if isinstance(g, int) else jax.lax.div(g, n)


def _fwd_kernel(offs_ref, *refs, plan: KernelPlan, causal: bool, window,
                mode: str, segments: bool):
    """One grid step of the forward: ``plan.heads`` heads x a resident
    [chunk_q, D] of Q x a resident [chunk_k, D] of K and V.

    Refs: q (G, chunk_q, D), k/v (Gkv, chunk_k, D), optional segment ids
    qs (G, chunk_q, 1) and ks (Gkv, chunk_k, 1) int32 (Gkv is
    ``plan.kv_heads``, G without a group), then the outputs of ``mode``
    — "plain": o;
    "train": o and the per-row lse (G, chunk_q, 1); "state" (ring
    attention): the UNnormalized accumulator plus (m, l), which the
    caller merges with the online-softmax combine — then scratch m/l
    (G, chunk_q, _STAT_LANES) and acc (G, chunk_q, D), which carry the
    state along the walk and across the sequential K-chunk grid dimension.
    offs = [q_off, k_off], global token offsets of sequence block 0."""
    n_in = 5 if segments else 3
    q_ref, k_ref, v_ref = refs[:3]
    qs_ref, ks_ref = refs[3:5] if segments else (None, None)
    outs = refs[n_in:-3]
    m_ref, l_ref, acc_ref = refs[-3:]
    G, tq, tk = plan.heads, plan.tile_q, plan.tile_k
    kv = _kv_head(plan)
    n_k = plan.chunk_k // tk
    n_kc = plan.grid[2]
    # program_id is read here, outside every predicated or looped body.
    kc = pl.program_id(2)
    q_base = offs_ref[0] + pl.program_id(1) * plan.chunk_q
    k_base = offs_ref[1] + kc * plan.chunk_k
    scale = 1.0 / (q_ref.shape[-1] ** 0.5)

    def q_tile(i):
        rows = _rows(i, tq)
        q_lo = q_base + i * tq

        def init():
            W = m_ref.shape[-1]
            m_ref[:, rows, :] = jnp.full((G, tq, W), NEG_INF, jnp.float32)
            l_ref[:, rows, :] = jnp.zeros((G, tq, W), jnp.float32)
            acc_ref[:, rows, :] = jnp.zeros((G, tq, acc_ref.shape[-1]),
                                            jnp.float32)

        def tile(j):
            cols = _rows(j, tk)
            mask = _tile_mask(q_lo, k_base + j * tk, tq, tk, causal, window)

            def head(g):
                # Feed the MXU its native input dtype (bf16 x bf16 -> f32
                # accumulate); pre-casting to f32 would halve throughput.
                c = kv(g)
                s = _mxu_dot(q_ref[g, rows, :], k_ref[c, cols, :],
                             ((1,), (1,))) * scale            # [tq, tk]
                keep = _keep(mask, qs_ref, ks_ref, g, c, rows, cols)
                if keep is not None:
                    s = jnp.where(keep, s, NEG_INF)
                m_prev = m_ref[g, rows, :]                    # [tq, W]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                # exp(NEG_INF - NEG_INF) is 1 and the state it scales 0.
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - _across(m_new, tk))
                if keep is not None:
                    # A row with no key yet has m_new == NEG_INF and
                    # exp(0) in every place.
                    p = jnp.where(keep, p, 0.0)
                l_new = (l_ref[g, rows, :] * corr +
                         jnp.sum(p, axis=-1, keepdims=True))
                # P rides the MXU in the V dtype (f32 accumulation
                # preserved) — the standard TPU flash-kernel trade.
                v = v_ref[c, cols, :]
                acc = (acc_ref[g, rows, :] * _across(corr, v.shape[-1]) +
                       _mxu_dot(p.astype(v.dtype), v, ((1,), (0,))))
                return m_new, l_new, acc

            def commit(g, state):
                m_ref[g, rows, :], l_ref[g, rows, :], acc_ref[g, rows, :] = \
                    state

            _for_heads(G, head, commit, plan.unroll)

        def finish():
            def head(g):
                m, l = m_ref[g, rows, :1], l_ref[g, rows, :1]
                acc = acc_ref[g, rows, :]
                if mode == "state":
                    outs[0][g, rows, :] = acc.astype(outs[0].dtype)
                    outs[1][g, rows, :] = m
                    outs[2][g, rows, :] = l
                else:
                    outs[0][g, rows, :] = (
                        acc / jnp.maximum(l, 1e-30)).astype(outs[0].dtype)
                    if mode == "train":
                        outs[1][g, rows, :] = row_lse(m, l)

            _for_heads(G, head)

        _walk(_k_bounds(q_lo, tq, k_base, tk, n_k, causal, window), tile,
              _ends(kc, n_kc), init, finish)

    _for_each(plan.chunk_q // tq, q_tile)


def _p_ds(q, k, v, do, lse, delta, keep, scale):
    """The backward's tile, whichever gradient it is for: P = exp(S -
    lse), rebuilt on-chip from the saved lse and masked by ``keep``, and
    dS = P * (dO.V^T - delta), both float32 [tq, tk]. delta = rowsum(dO *
    O), precomputed by the caller. The softmax scale is left out of dS:
    it multiplies the dQ and dK accumulators once, at the end."""
    s = _mxu_dot(q, k, ((1,), (1,))) * scale
    p = jnp.exp(s - lse)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = _mxu_dot(do, v, ((1,), (1,)))
    return p, p * (dp - delta)


def _dq_kernel(offs_ref, *refs, plan: KernelPlan, causal: bool, window,
               segments: bool):
    """dQ pass of the two-pass backward: the forward's walk. dQ
    accumulates dS.K in VMEM along the walk and across the sequential
    K-chunk grid dimension."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    qs_ref, ks_ref = refs[6:8] if segments else (None, None)
    dq_ref, dq_acc = refs[-2:]
    G, tq, tk = plan.heads, plan.tile_q, plan.tile_k
    kv = _kv_head(plan)
    n_k = plan.chunk_k // tk
    n_kc = plan.grid[2]
    kc = pl.program_id(2)
    q_base = offs_ref[0] + pl.program_id(1) * plan.chunk_q
    k_base = offs_ref[1] + kc * plan.chunk_k
    scale = 1.0 / (q_ref.shape[-1] ** 0.5)

    def q_tile(i):
        rows = _rows(i, tq)
        q_lo = q_base + i * tq

        def init():
            dq_acc[:, rows, :] = jnp.zeros((G, tq, dq_acc.shape[-1]),
                                           jnp.float32)

        def tile(j):
            cols = _rows(j, tk)
            mask = _tile_mask(q_lo, k_base + j * tk, tq, tk, causal, window)

            def head(g):
                c = kv(g)
                k = k_ref[c, cols, :]
                _, ds = _p_ds(
                    q_ref[g, rows, :], k, v_ref[c, cols, :],
                    do_ref[g, rows, :], lse_ref[g, rows, :],
                    delta_ref[g, rows, :],
                    _keep(mask, qs_ref, ks_ref, g, c, rows, cols), scale)
                return dq_acc[g, rows, :] + _mxu_dot(
                    ds.astype(k.dtype), k, ((1,), (0,)))          # [tq, D]

            def commit(g, dq):
                dq_acc[g, rows, :] = dq

            _for_heads(G, head, commit, plan.unroll)

        def finish():
            def head(g):
                dq_ref[g, rows, :] = (dq_acc[g, rows, :] *
                                      scale).astype(dq_ref.dtype)

            _for_heads(G, head)

        _walk(_k_bounds(q_lo, tq, k_base, tk, n_k, causal, window), tile,
              _ends(kc, n_kc), init, finish)

    _for_each(plan.chunk_q // tq, q_tile)


def _bwd_kernel(offs_ref, *refs, plan: KernelPlan, causal: bool, window,
                segments: bool, fused: bool):
    """The backward's transposed walk: for each K sub-tile, the Q
    sub-tiles that see it, in the forward's [tq, tk] orientation; the
    transposed contractions (P^T.dO, dS^T.Q) ride dot_general dimension
    numbers so no tile is ever explicitly transposed. The softmax scale
    multiplies the dK and dQ accumulators once, at the end.

    ``fused`` (``flash_bwd``): dQ, dK and dV from one ``_p_ds`` a tile.
    Grid (K/V heads, passes, Q chunk, K chunk), the last three
    sequential. dK and dV accumulate in (Gkv, Tk, D) float32 scratch, a
    K/V head's whole sequence, from the head's first Q chunk to its last
    and leave then, a K chunk each step; dQ accumulates dS.K in (G,
    chunk_q, D) across the K chunks, the K sub-tiles in ascending order,
    as the dQ pass adds them.

    Not fused (``flash_dkv``, the two-pass backward's second): dK and dV
    alone, grid (K/V heads, K chunk, Q chunk), sequential over Q chunks,
    the accumulators a resident chunk's.

    Either way a K/V head's accumulators take every query head of its
    group: the step's own that share it (each body's products are added
    once all of the body are computed), and, where the step holds fewer
    than the group, those of the ``plan.passes`` steps that walk the Q
    chunks again on a sequential dimension, a head block each."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    qs_ref, ks_ref = refs[6:8] if segments else (None, None)
    if fused:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[-6:]
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs[-4:]
    G, Gkv, tq, tk = plan.heads, plan.kv_heads, plan.tile_q, plan.tile_k
    kv = _kv_head(plan)
    shared = plan.shared > 1
    n_q, n_k = plan.chunk_q // tq, plan.chunk_k // tk
    if fused:
        _, n_p, n_qc, n_kc = plan.grid
        qc = pl.program_id(2)
        kc = pl.program_id(3) if n_kc > 1 else 0
        q_ends = tuple(map(_both, _ends(pl.program_id(1), n_p),
                           _ends(qc, n_qc)))
        k_ends = _ends(kc, n_kc)
    else:
        n_seq = plan.grid[2]          # the Q chunks, ``passes`` times over
        step = pl.program_id(2)
        qc = (step if plan.passes == 1
              else jax.lax.rem(step, n_seq // plan.passes))
        kc = pl.program_id(1)
        q_ends = _ends(step, n_seq)
    q_base = offs_ref[0] + qc * plan.chunk_q
    k_base = offs_ref[1] + kc * plan.chunk_k
    scale = 1.0 / (q_ref.shape[-1] ** 0.5)

    if fused:
        def zero_dq(i):
            dq_acc[:, _rows(i, tq), :] = jnp.zeros(
                (G, tq, dq_acc.shape[-1]), jnp.float32)

        _when(k_ends[0], lambda: _for_each(n_q, zero_dq))

    def k_tile(j):
        cols = _rows(j, tk)
        # The tile's rows in the accumulators.
        held = _rows(kc * n_k + j, tk) if fused else cols
        k_lo = k_base + j * tk

        def init():
            zeros = jnp.zeros((Gkv, tk, dk_acc.shape[-1]), jnp.float32)
            dk_acc[:, held, :] = zeros
            dv_acc[:, held, :] = zeros

        def tile(i):
            rows = _rows(i, tq)
            mask = _tile_mask(q_base + i * tq, k_lo, tq, tk, causal, window)

            def head(g):
                c = kv(g)

                def add(acc, product):
                    # Heads of a body that share an accumulator hand
                    # their products to ``commit``.
                    return product if shared else acc[c, held, :] + product

                q, k = q_ref[g, rows, :], k_ref[c, cols, :]
                do = do_ref[g, rows, :]
                p, ds = _p_ds(
                    q, k, v_ref[c, cols, :], do, lse_ref[g, rows, :],
                    delta_ref[g, rows, :],
                    _keep(mask, qs_ref, ks_ref, g, c, rows, cols), scale)
                ds = ds.astype(q.dtype)
                dv = add(dv_acc, _mxu_dot(p.astype(do.dtype), do,
                                          ((0,), (0,))))          # [tk, D]
                dk = add(dk_acc, _mxu_dot(ds, q, ((0,), (0,))))   # [tk, D]
                if not fused:
                    return dk, dv
                return dk, dv, dq_acc[g, rows, :] + _mxu_dot(
                    ds, k, ((1,), (0,)))                          # [tq, D]

            def commit(g, grads):
                c = kv(g)
                dk, dv = grads[:2]
                if shared:
                    dk, dv = dk_acc[c, held, :] + dk, dv_acc[c, held, :] + dv
                dk_acc[c, held, :], dv_acc[c, held, :] = dk, dv
                if fused:
                    dq_acc[g, rows, :] = grads[2]

            _for_heads(G, head, commit, plan.unroll)

        def finish():
            def head(g):
                dk_ref[g, held, :] = (dk_acc[g, held, :] *
                                      scale).astype(dk_ref.dtype)
                dv_ref[g, held, :] = dv_acc[g, held, :].astype(dv_ref.dtype)

            _for_heads(Gkv, head)

        _walk(_q_bounds(k_lo, tk, q_base, tq, n_q, causal, window), tile,
              q_ends, init, finish)

    _for_each(n_k, k_tile)

    if fused:
        def write_dq(i):
            rows = _rows(i, tq)

            def head(g):
                dq_ref[g, rows, :] = (dq_acc[g, rows, :] *
                                      scale).astype(dq_ref.dtype)

            _for_heads(G, head)

        _when(k_ends[1], lambda: _for_each(n_q, write_dq))


def _block_shape(plan, side, shape):
    """The block of a merged ``shape`` array that follows ``side``: "q"
    or "k", a step's heads and resident chunk of that side; "K", the K
    side's heads and the whole sequence (the fused backward's dk, dv and
    their accumulators)."""
    heads = plan.heads if side == "q" else plan.kv_heads
    rows = {"q": plan.chunk_q, "k": plan.chunk_k}.get(side, shape[1])
    return heads, rows, shape[-1]


def _flash_call(kind, kernel, plan, args, out_shapes, out_sides, scratch,
                interpret):
    """The ``pallas_call`` of one pass. ``args`` are (array, side) pairs,
    side "q", "k" or "K" saying which chunk and which head count the
    block follows (``_block_shape``); the outputs' sides are ``out_sides``.
    The last grid dimension is the sequential one: K chunks for "fwd"
    and "dq", Q chunks for "dkv"; of "bwd"'s four, all but the first.

    A group's ``plan.passes`` head blocks share one K-side block: the
    forward and the dQ pass send grid row ``b`` to K/V block ``b //
    passes``; the backward and the dK/dV pass, whose rows are K/V blocks,
    take the group's head blocks one after another on a sequential
    dimension: the fused backward's second (head block ``b * passes +
    i``), and, for "dkv", the one it shares with the Q chunks (head block
    ``b * passes + i // n_qc``, Q chunk ``i % n_qc``)."""
    n = plan.passes
    if kind == "bwd":
        maps = {"q": lambda b, i, qc, kc, offs: (b * n + i, qc, 0),
                "k": lambda b, i, qc, kc, offs: (b, kc, 0),
                "K": lambda b, i, qc, kc, offs: (b, 0, 0)}
    elif kind == "dkv":
        maps = {"q": lambda b, kc, qc, offs: (b, qc, 0),
                "k": lambda b, kc, qc, offs: (b, kc, 0)}
        if n > 1:
            n_qc = plan.grid[2] // n
            maps["q"] = lambda b, kc, i, offs: (
                b * n + jax.lax.div(i, n_qc), jax.lax.rem(i, n_qc), 0)
    else:
        maps = {"q": lambda b, qc, kc, offs: (b, qc, 0),
                "k": lambda b, qc, kc, offs: (b, kc, 0)}
        if n > 1:
            maps["k"] = lambda b, qc, kc, offs: (jax.lax.div(b, n), kc, 0)

    def spec(shape, side):
        return pl.BlockSpec(_block_shape(plan, side, shape), maps[side])

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=plan.grid,
            in_specs=[spec(a.shape, side) for a, side in args],
            out_specs=[spec(o.shape, side)
                       for o, side in zip(out_shapes, out_sides)],
            scratch_shapes=scratch,
        ),
        out_shape=out_shapes,
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3
            if kind == "bwd" else ("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_DEFAULT_LIMIT,
                                 plan.vmem_bytes + (8 << 20))),
        interpret=interpret,
        name=f"flash_{kind}",
    )


def _seg_args(q_seg, k_seg):
    """[BH, Tq] and [BHkv, Tk] int32 -> [.., T, 1] blocks, the
    row-oriented layout the lse/delta blocks already use. Mosaic requires
    the last two block dims be (8, 128)-divisible or full-extent; a (G,
    chunk, 1) block satisfies that for EVERY chunk (>= 8 on the sublane
    dim, the lane dim full at 1) — the lane-major (G, 1, chunk) layout
    fails for sub-tiles < 128."""
    if q_seg is None:
        return []
    return [(q_seg[:, :, None], "q"), (k_seg[:, :, None], "k")]


def _flash_forward(q, k, v, offs, causal: bool, interpret: bool, mode: str,
                   q_seg=None, k_seg=None, window=None):
    """The forward pass on merged operands, q [BH, Tq, D] over k, v
    [BHkv, Tk, D]. ``mode`` "plain": o in q.dtype; "train": (o, lse f32
    [BH, Tq, 1]); "state": (acc f32, m, l), the unmerged online-softmax
    state of this K block."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    segments = q_seg is not None
    plan = kernel_plan(BH, Tq, Tk, D, q.dtype, causal, window,
                       segments=segments, kind="fwd",
                       state=mode == "state", group=BH // k.shape[0])
    _log_plan("fwd", q.shape, q.dtype, causal, window, plan)
    row = jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32)
    out_shapes = {
        "plain": [jax.ShapeDtypeStruct(q.shape, q.dtype)],
        "train": [jax.ShapeDtypeStruct(q.shape, q.dtype), row],
        "state": [jax.ShapeDtypeStruct(q.shape, jnp.float32), row, row],
    }[mode]
    G, cq = plan.heads, plan.chunk_q
    operands = [(q, "q"), (k, "k"), (v, "k")] + _seg_args(q_seg, k_seg)
    out = _flash_call(
        "fwd",
        functools.partial(_fwd_kernel, plan=plan, causal=causal,
                          window=window, mode=mode, segments=segments),
        plan, operands, out_shapes, ["q"] * len(out_shapes),
        [pltpu.VMEM((G, cq, _STAT_LANES), jnp.float32),
         pltpu.VMEM((G, cq, _STAT_LANES), jnp.float32),
         pltpu.VMEM((G, cq, D), jnp.float32)],
        interpret,
    )(offs, *(a for a, _ in operands))
    return out[0] if mode == "plain" else tuple(out)


def _pallas_bwd(q, k, v, do, lse, delta, offs, causal: bool,
                interpret: bool, out_dtype=None, q_seg=None, k_seg=None,
                window=None):
    """The flash backward; returns (dq, dk, dv) in the input dtypes (or
    ``out_dtype`` when given — ring accumulation wants f32), dk and dv
    at the head count of k and v. lse/delta: f32 [BH, T, 1].

    One fused kernel where ``kernel_plan`` has a "bwd" plan for the
    shape, else the two passes."""
    BH, Tq, D = q.shape
    BHkv, Tk = k.shape[:2]
    segments = q_seg is not None
    operands = [(q, "q"), (k, "k"), (v, "k"), (do, "q"), (lse, "q"),
                (delta, "q")] + _seg_args(q_seg, k_seg)

    def plan_of(kind):
        return kernel_plan(BH, Tq, Tk, D, q.dtype, causal, window,
                           segments=segments, kind=kind,
                           out_dtype=out_dtype, group=BH // BHkv)

    def run(kind, plan, outs):
        """``outs``: "q" for dq, "k" for dk and dv, in the kernel's
        order; the accumulators follow them, whole-sequence for "K"."""
        _log_plan(kind, q.shape, q.dtype, causal, window, plan)
        like = {"q": q, "k": k, "K": k}
        static = dict(plan=plan, causal=causal, window=window,
                      segments=segments)
        return _flash_call(
            kind,
            functools.partial(_dq_kernel, **static) if kind == "dq"
            else functools.partial(_bwd_kernel, fused=kind == "bwd",
                                   **static),
            plan, operands,
            [jax.ShapeDtypeStruct(like[o].shape,
                                  out_dtype or like[o].dtype) for o in outs],
            outs,
            [pltpu.VMEM(_block_shape(plan, o, like[o].shape), jnp.float32)
             for o in outs],
            interpret,
        )(offs, *(a for a, _ in operands))

    plan = plan_of("bwd")
    if plan is not None:
        return tuple(run("bwd", plan, ["q", "K", "K"]))
    _log.debug(f"flash_bwd {tuple(q.shape)} over {Tk} keys: no fused plan "
               f"(dK and dV for a K/V head's {Tk} rows do not fit "
               f"{BWD_VMEM_BUDGET} B); the two passes run")
    return (*run("dq", plan_of("dq"), ["q"]),
            *run("dkv", plan_of("dkv"), ["k", "k"]))


# ---------------------------------------------------------------------------
# XLA twins, dispatch and the public entry points.
# ---------------------------------------------------------------------------


def int_cotangent(x):
    """Symbolic-zero cotangent for an optional integer array argument of
    a custom_vjp (None passes through)."""
    import numpy as np

    return None if x is None else np.zeros(x.shape,
                                           dtype=jax.dtypes.float0)


def _apply_segment_mask(x, q_seg, k_seg, fill):
    """Packed-sequence masking, the single definition: positions with
    differing segment ids take ``fill`` (NEG_INF on scores, 0 on
    probabilities). x: [BH, Tq, Tk]; q_seg/k_seg: int32 [BH, T]."""
    return jnp.where(q_seg[:, :, None] == k_seg[:, None, :], x, fill)


def _require_both_segs(q_seg, k_seg):
    if (q_seg is None) != (k_seg is None):
        raise ValueError("pass both q_segment_ids and k_segment_ids")


def _mask_rule(window, blocks, causal):
    """What rides in the kernels' ``window`` slot: the window itself, or
    the ``BlockCausal`` of ``blocks`` = (q_block, k_block); either needs
    ``causal`` and they exclude each other."""
    if window is None and blocks is None:
        return None
    if not causal:
        raise ValueError("sliding-window attention is defined for the "
                         "causal case; pass causal=True with window")
    if blocks is None:
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        return window
    if window is not None or min(blocks) < 1:
        raise ValueError(
            f"blocks = (q_block, k_block) >= 1 is a mask rule of its own, "
            f"not one under a window; got blocks {blocks}, window {window}")
    return BlockCausal(*map(int, blocks))


def _xla_expand(q, *k_side):
    """The twins attend at the query heads' count: each merged K-side
    array ([BHkv, ...], or None) repeated to ``q``'s BH rows, query head j
    reading K/V head j // g. The repeat's transpose sums the groups."""
    g = q.shape[0] // k_side[0].shape[0]
    return [x if x is None or g == 1 else jnp.repeat(x, g, axis=0)
            for x in k_side]


@jax.named_scope("flash_xla")
def _xla_block_state(q, k, v, offs, causal, q_seg=None, k_seg=None,
                     window=None):
    """XLA twin of the block-mode kernel (backward recompute + fallback).
    ``offs`` = int32[2] (q_off, k_off) — an array, not statics, because
    ring attention traces the rotating block origin. ``q_seg``/``k_seg``:
    optional int32 [BH, T] / [BHkv, T] per-block segment ids (packed
    sequences)."""
    k, v, k_seg = _xla_expand(q, k, v, k_seg)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + offs[0]
        ik = jnp.arange(k.shape[1])[None, :] + offs[1]
        if isinstance(window, BlockCausal):
            s = jnp.where(_block_visible(iq, ik, window), s, NEG_INF)
        else:
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
    if not _kernels_take(("fwd",), q, k, causal, window, q_seg is not None,
                         state=True):
        return _xla_block_state(q, k, v, offs, causal, q_seg=q_seg,
                                k_seg=k_seg, window=window)
    return _flash_forward(q, k, v, offs, causal, interpret, "state",
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
    """[B, T, H, D] -> [B*H, T, D], each array at its own head count."""
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _split_heads(x, B):
    """[B*H, T, D] -> [B, T, H, D]: ``_merge_heads`` back."""
    BH, T, D = x.shape
    return x.reshape(B, BH // B, T, D).transpose(0, 2, 1, 3)


def _kv_heads(q, k, v):
    """Hkv of ``k``, ``v`` [B, T, Hkv, D], which divides the H of ``q``
    [B, T, H, D]: query head j reads K/V head j // (H / Hkv). The group
    is read from these shapes and nothing else."""
    H, Hkv = q.shape[2], k.shape[2]
    if v.shape[2] != Hkv or H % Hkv:
        raise ValueError(
            f"{H} query heads over {Hkv} K and {v.shape[2]} V heads: the "
            "K/V head count must be one and divide the query heads'")
    return Hkv


def _block_offsets(q_off, k_off):
    """int32[2], what the kernels prefetch: traced where ring attention
    rotates the K block."""
    return jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])


def _tiled_segs(q_segment_ids, k_segment_ids, H, Hkv):
    """The two sides' [B, T] segment ids at their merged head counts, or
    (None, None)."""
    _require_both_segs(q_segment_ids, k_segment_ids)
    if q_segment_ids is None:
        return None, None
    return _tile_seg(q_segment_ids, H), _tile_seg(k_segment_ids, Hkv)


def flash_attention_block_merged(q, k, v, offs, causal: bool = True,
                                 use_pallas: Optional[bool] = None,
                                 q_seg=None, k_seg=None,
                                 window: Optional[int] = None, blocks=None):
    """``flash_attention_block`` on operands whose heads are merged
    already: q [BH, Tq, D], k/v [BHkv, Tk, D] (the module's docstring on
    head counts), ``offs`` int32[2] = (q_off, k_off), ``q_seg``/``k_seg``
    int32 [BH, Tq] / [BHkv, Tk] or None. Returns (acc f32 [BH, Tq, D], m,
    l f32 [BH, Tq, 1]) as the kernel leaves them. For a caller that hands
    several calls one merged array (``ops/eva_attention``); the kernels
    where ``kernel_plan`` has a step for the shape, else the XLA twin."""
    window = _mask_rule(window, blocks, causal)
    use_pallas, interpret = _resolve_dispatch(use_pallas)
    if use_pallas:
        return _block_state_core(q, k, v, offs, q_seg, k_seg, causal,
                                 interpret, window)
    return _xla_block_state(q, k, v, offs, causal, q_seg=q_seg,
                            k_seg=k_seg, window=window)


def flash_attention_block(q, k, v, q_off, k_off, causal: bool = True,
                          use_pallas: Optional[bool] = None,
                          q_segment_ids=None, k_segment_ids=None,
                          window: Optional[int] = None, blocks=None):
    """One K/V block's unmerged attention state for ring attention, and
    for one softmax over two key sets on one chip (``ops/eva_attention``,
    through ``flash_attention_block_merged``: ``blocks`` = (q_block,
    k_block) puts the block-causal rule in the diagonal's place, the
    module's docstring).

    q: [B, T, H, D]; k/v: [B, T, Hkv, D], Hkv a divisor of H (query head
    j reads K/V head j // (H / Hkv)). Returns (acc, m, l) with acc f32
    [B, T, H, D]
    (unnormalized P.V), m/l f32 [B, H, T] — merge across blocks with the
    online-softmax combine. Dispatch rules match ``flash_attention``
    (shared ``_resolve_dispatch``); segment ids stream into the same
    kernels as extra id blocks (packed sequences).
    """
    B, Tq, H, _ = q.shape
    Hkv = _kv_heads(q, k, v)
    offs = _block_offsets(q_off, k_off)
    q_seg, k_seg = _tiled_segs(q_segment_ids, k_segment_ids, H, Hkv)
    acc, m, l = flash_attention_block_merged(
        _merge_heads(q), _merge_heads(k), _merge_heads(v), offs, causal,
        use_pallas, q_seg, k_seg, window, blocks)
    return _split_heads(acc, B), m.reshape(B, H, Tq), l.reshape(B, H, Tq)


def flash_attention_block_grads_merged(q, k, v, do, lse, delta, offs,
                                       causal: bool = True,
                                       use_pallas: Optional[bool] = None,
                                       q_seg=None, k_seg=None,
                                       window: Optional[int] = None,
                                       blocks=None, out_dtype=jnp.float32):
    """``flash_attention_block_grads`` on merged operands: q/do [BH, Tq,
    D], k/v [BHkv, Tk, D], lse/delta f32 [BH, Tq, 1] (the columns the
    kernels take), ``offs`` and the segment ids as
    ``flash_attention_block_merged``. Returns (dq [BH, Tq, D], dk, dv
    [BHkv, Tk, D]) of ``out_dtype``."""
    window = _mask_rule(window, blocks, causal)
    use_pallas, interpret = _resolve_dispatch(use_pallas)
    if use_pallas and _kernels_take(("dq", "dkv"), q, k, causal, window,
                                    q_seg is not None, out_dtype=out_dtype):
        return _pallas_bwd(q, k, v, do, lse, delta, offs, causal, interpret,
                           out_dtype=out_dtype, q_seg=q_seg, k_seg=k_seg,
                           window=window)
    return _xla_block_grads(q, k, v, do, lse, delta, offs, causal,
                            out_dtype=out_dtype, q_seg=q_seg, k_seg=k_seg,
                            window=window)


def flash_attention_block_grads(q, k, v, do, lse, delta, q_off, k_off,
                                causal: bool = True,
                                use_pallas: Optional[bool] = None,
                                q_segment_ids=None, k_segment_ids=None,
                                window: Optional[int] = None, blocks=None,
                                out_dtype=jnp.float32):
    """One K/V block's (dq, dk, dv) for ring attention's backward pass
    (and ``ops/eva_attention``'s, through
    ``flash_attention_block_grads_merged``; ``blocks`` as
    ``flash_attention_block``).

    q/do: [B, T, H, D]; k/v: [B, T, Hkv, D]; lse/delta: f32 [B, H, T] —
    the GLOBAL row statistics (lse over all keys, delta = rowsum(dO*O)),
    so each block's P = exp(S - lse) is already globally normalized and
    the per-block gradients simply sum across the ring. Returns arrays
    of ``out_dtype`` in the layout of q, k and v, dk and dv summed over
    each group of query heads (f32 by default so the ring's cross-block
    accumulation doesn't round at the model dtype each step).
    """
    B, Tq, H, _ = q.shape
    Hkv = _kv_heads(q, k, v)
    offs = _block_offsets(q_off, k_off)
    qm, km, vm, dom = (_merge_heads(x) for x in (q, k, v, do))
    lse_m = lse.reshape(B * H, Tq, 1)
    delta_m = delta.reshape(B * H, Tq, 1)
    q_seg, k_seg = _tiled_segs(q_segment_ids, k_segment_ids, H, Hkv)
    dq, dk, dv = flash_attention_block_grads_merged(
        qm, km, vm, dom, lse_m, delta_m, offs, causal, use_pallas, q_seg,
        k_seg, window, blocks, out_dtype)
    return tuple(_split_heads(x, B) for x in (dq, dk, dv))


@jax.named_scope("flash_xla")
def _xla_block_grads(q, k, v, do, lse, delta, offs, causal: bool,
                     out_dtype=None, q_seg=None, k_seg=None, window=None):
    """XLA twin of the backward kernel (fallback for untileable shapes
    and non-TPU platforms). Same math, same lse/delta residuals; dk and
    dv summed over each group in float32, at the head count of k and v."""
    dq_dt = out_dtype or q.dtype
    dk_dt = out_dtype or k.dtype
    dv_dt = out_dtype or v.dtype
    BHkv = k.shape[0]
    k, v, k_seg = _xla_expand(q, k, v, k_seg)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jnp.exp(s - lse)
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + offs[0]
        ik = jnp.arange(k.shape[1])[None, :] + offs[1]
        if isinstance(window, BlockCausal):
            p = jnp.where(_block_visible(iq, ik, window)[None], p, 0.0)
        else:
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
    if BHkv != q.shape[0]:
        dk, dv = (x.reshape(BHkv, -1, *x.shape[1:]).sum(1) for x in (dk, dv))
    return dq.astype(dq_dt), dk.astype(dk_dt), dv.astype(dv_dt)


@jax.named_scope("flash_xla")
def _xla_flash(q, k, v, q_off, k_off, causal, q_seg=None, k_seg=None,
               window=None):
    """XLA reference path (backward recompute + non-TPU fallback), fp32
    accumulation — the same math as parallel.ring_attention.
    ``q_seg``/``k_seg``: optional int32 [BH, T] / [BHkv, T] segment ids
    (packed sequences); tokens attend only within their segment."""
    k, v, k_seg = _xla_expand(q, k, v, k_seg)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        iq = jnp.arange(q.shape[1])[:, None] + q_off
        ik = jnp.arange(k.shape[1])[None, :] + k_off
        if isinstance(window, BlockCausal):
            s = jnp.where(_block_visible(iq, ik, window), s, NEG_INF)
        else:
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
    if not _kernels_take(("fwd",), q, k, causal, window, q_seg is not None):
        return _xla_flash(q, k, v, q_off, k_off, causal, q_seg=q_seg,
                          k_seg=k_seg, window=window)
    return _flash_forward(q, k, v, jnp.asarray([q_off, k_off], jnp.int32),
                          causal, interpret, "plain", q_seg=q_seg,
                          k_seg=k_seg, window=window)


def _flash_fwd(q, k, v, q_seg, k_seg, q_off, k_off, causal, interpret,
               window):
    if not _kernels_take(("fwd", "dq", "dkv"), q, k, causal, window,
                         q_seg is not None):
        return _xla_flash(q, k, v, q_off, k_off, causal, q_seg=q_seg,
                          k_seg=k_seg, window=window), \
            (q, k, v, q_seg, k_seg, None, None)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    o, lse = _flash_forward(q, k, v, offs, causal, interpret, "train",
                            q_seg=q_seg, k_seg=k_seg, window=window)
    # Named for a caller's ``jax.checkpoint`` policy: a layer that keeps
    # both runs no forward kernel when it is rematerialized.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
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
                    window: Optional[int] = None, blocks=None):
    """Blocked flash attention. q: [B, T, H, D]; k/v: [B, T, Hkv, D] with
    Hkv a divisor of H: query head j reads K/V head j // (H / Hkv)
    (grouped-query attention; Hkv == H is multi-head). The kernels fetch
    a K/V head for its group through their index maps and sum a group's
    dK and dV in VMEM: K, V and their gradients stay at Hkv heads.

    ``use_pallas=None`` auto-selects via ``_resolve_dispatch``.
    ``q_off``/``k_off`` are the global token offsets of the blocks — ring
    attention passes the rotating K block's origin so causal masking stays
    globally correct.

    ``q_segment_ids``/``k_segment_ids`` (int [B, T]): packed-sequence
    masking — a token attends only to keys with its segment id (composed
    with the causal mask). The Mosaic kernels stream the ids as extra
    (heads, chunk, 1) int32 blocks; the mask composes at trace time so
    the segment-free path compiles unchanged.

    ``blocks`` = (q_block, k_block): the block-causal rule in the
    diagonal's place (the module's docstring); k and v may then be
    shorter than q, a row a block's summary.
    """
    B, Tq, H, D = q.shape
    Hkv = _kv_heads(q, k, v)

    def split(x, t):
        return x.reshape(B, H, t, D).transpose(0, 2, 1, 3)

    _require_both_segs(q_segment_ids, k_segment_ids)
    window = _mask_rule(window, blocks, causal)
    q_seg = k_seg = None
    if q_segment_ids is not None:
        q_seg = _tile_seg(q_segment_ids, H)
        k_seg = _tile_seg(k_segment_ids, Hkv)

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
