"""Grouped matrix products over ragged groups of rows, as Pallas kernels.

The rows of ``lhs`` [m, k] are consecutive groups of ``group_sizes`` rows
(data; every shape static), each with a matrix of its own:

``gmm``   ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (``rhs`` [G, k, n],
          or [G, n, k] with ``transpose_rhs``: the row gradient);
``tgmm``  ``out[g] = lhs[rows of g]^T @ rhs[rows of g]`` (``rhs`` [m, n]:
          the matrices' gradient, and a sorted segment sum where ``lhs``
          is an indicator).

The scheme is that of jax's own Pallas grouped matmul for the TPU (group
metadata as prefetched scalars, a data-sized grid over the row tiles the
groups touch, float32 accumulation, one cast at the store): the rows are
cut into tiles of ``plan.tm`` at multiples of ``tm``, and a tile is
visited once for every group that has a row in it, consecutively. Two
things are this module's.

The work of a visit goes with the rows the group has in the tile. A tile
that lies wholly inside the group is multiplied unmasked, whole or by
``plan.part`` rows where the whole product does not fit. Any other
visit walks the tile in strips of ``plan.strip`` rows (a loop, so the
program does not grow with the tile) and multiplies only the strips that
hold a row of the group; of those only a strip that a boundary cuts takes
the row mask (``gmm``: a select against what the output block holds;
``tgmm``: both operands' rows outside the group made zero by a select,
so that nothing there, finite or not, reaches a sum).

``gmm`` takes the contraction whole: a block of the matrix is [k, tn],
named by (group, column tile) alone, so it is fetched once a group, by
the kernel's own copy into one of two slots, asked for at the first visit
of the group before (a visit that a boundary cut to one strip is too
short to fetch 4 MiB under); and consecutive visits of one row tile name
the same ``lhs`` block, which Pallas then fetches once a tile. ``tgmm``
takes an output block [tk, tn] as large as fits, so the rows are read
``k / tk`` and ``n / tn`` times. ``kernel_plan`` decides the tiles from
the shapes, by a count of their VMEM held under ``VMEM_BUDGET``: the
scoped VMEM every Mosaic call has without asking, so the calls set no
limit of their own and XLA assigns its own buffers around them as around
any other call.

What a caller may rely on: a row of ``gmm`` sums the terms of its product
in float32; ``tgmm`` sums a group's rows strip by strip in float32; a
group without rows leaves a zero matrix in ``tgmm``'s result and is not
visited by ``gmm``, so a stack of ``L * E`` groups of which one layer's
have rows is read in place. Rows past the last group are not written by
``gmm``: a tile no group touches is never visited, and whatever the buffer
held stays there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import logging as _log
from ..common.compat import pallas_tpu_compiler_params as _compiler_params
from .pallas_attention import _mxu_dot

# Rows of a visit's tile: what a grid step fetches of ``lhs``, and the
# unit callers size their row buffers by (``parallel/moe.py``'s windows).
ROW_TILE = 512
# Rows multiplied at a time in a tile that a group boundary cuts: one MXU
# pass high.
_STRIP = 128
_LANES = 128
# What a plan may count: the scoped VMEM a Mosaic call has by default on a
# v5e. The calls ask for no more (no ``vmem_limit_bytes``): XLA keeps
# buffers of its own in VMEM (a step of trinity-mini's has 64 MiB of them)
# and makes room around a custom call for the call's limit, and with limits
# of 20 to 48 MiB on these calls the chip's compiler left 127 MiB more of
# holes in that step's HBM and crashed repacking VMEM in two programs
# (``BestFitRepacker::Finish``; PERF.md section 6, PR 41). Under the
# default it assigns VMEM as it did around the kernels these replace.
VMEM_BUDGET = 16 << 20

KINDS = ("gmm", "gmm_t", "tgmm")


class MatmulPlan(NamedTuple):
    """What a grouped matmul's ``pallas_call`` does, all of it static."""
    tm: int          # rows of a tile: a grid step's block of lhs
    strip: int       # rows multiplied at a time where a boundary cuts
    part: int        # rows multiplied at a time in a tile inside its group
    tk: int          # gmm: the contraction, whole; tgmm: rows of an
    #                  output block (columns of lhs a step)
    tn: int          # columns of a block of the matrices and the result
    vmem_bytes: int  # counted VMEM, at most ``VMEM_BUDGET``


def _vmem_bytes(kind, tm, part, tk, tn, itemsize, out_itemsize):
    """VMEM one grid step holds: the pipelined blocks twice (``gmm``'s
    matrix in its two slots), ``tgmm``'s accumulator, and what Mosaic
    keeps of the rows it multiplies at a time: a copy of ``part`` rows by
    ``tk`` of bf16, the bf16 parts of float32 rows under
    ``jax.default_matmul_precision("highest")`` (four times their bytes),
    a slice of the float32 product, 256 KiB of its own. Held against what
    the chip's compiler refuses at a described v5e (the least limit it
    takes, at twenty plans of both types): over by 0.2 to 0.7 MiB."""
    if kind == "tgmm":
        blocks = (2 * tm * (tk + tn) * itemsize
                  + tk * tn * (2 * out_itemsize + 4))
    else:
        blocks = (2 * (tm * tk + tk * tn) * itemsize
                  + 2 * tm * tn * out_itemsize)
    rows = part * tk * (2 if itemsize == 2 else 16)
    return blocks + rows + part * 1024 + (256 << 10)


def _divisors(size):
    """The blocks a dimension of ``size`` may be cut into, largest first:
    itself, then its divisors on the lane grid."""
    return [size] + [t for t in range(size - size % _LANES, 0, -_LANES)
                     if t != size and size % t == 0]


def kernel_plan(m, k, n, groups, dtype, kind="gmm", out_dtype=None):
    """The tiles of the grouped product ``kind`` (``KINDS``; "gmm_t" is
    ``gmm`` with the matrices transposed) of ``m`` rows by ``groups``
    matrices [k, n] in ``dtype``: a pure function of the shapes. None
    where nothing fits ``VMEM_BUDGET``: a contraction too long to hold
    whole beside 128 columns.

    Rows: ``ROW_TILE``, or what of it divides ``m``; strips of 128 of
    them, or the tile where it is shorter. ``gmm``: the contraction whole
    and the most columns that fit, all of them first, so that ``lhs`` is
    read once; a tile inside its group is multiplied whole where that
    fits beside them, else by halves, quarters, strips. ``tgmm``: the
    largest output block [tk, tn] that fits, the whole matrix first, then
    halving the side that leaves the fewer re-reads of the rows.
    ``groups`` moves nothing today (the tables are scalars in SMEM); it
    is what a caller knows, and stays in the key."""
    del groups
    itemsize = jnp.dtype(dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype or dtype).itemsize
    tm = math.gcd(m, ROW_TILE)
    strip = math.gcd(tm, _STRIP)
    if kind != "tgmm":
        # tm and strip divide 512: powers of two.
        parts = [tm >> i for i in range((tm // strip).bit_length())]
        for tn in _divisors(n):
            for part in parts:
                vmem = _vmem_bytes(kind, tm, part, k, tn, itemsize,
                                   out_itemsize)
                if vmem <= VMEM_BUDGET:
                    return MatmulPlan(tm, strip, part, k, tn, vmem)
        return None
    fits = [(k // tk * n + n // tn * k, -tk * tn, tk, tn, vmem)
            for tk in _divisors(k) for tn in _divisors(n)
            for vmem in [_vmem_bytes(kind, tm, tm, tk, tn, itemsize,
                                     out_itemsize)] if vmem <= VMEM_BUDGET]
    if not fits:
        return None
    return MatmulPlan(tm, strip, tm, *min(fits)[2:])


def _log_plan(kind, shape, dtype, plan):
    """Everything a plan decides is static, so it is logged once, when
    the call is traced (``HOROVOD_LOG_LEVEL=debug``)."""
    _log.debug(
        f"{kind} {tuple(shape)} {jnp.dtype(dtype).name}: tiles of "
        f"{plan.tm} rows by {plan.part}, in strips of {plan.strip} at a "
        f"boundary, blocks [{plan.tk}, {plan.tn}], VMEM {plan.vmem_bytes} B")


def _visits(group_sizes, m, tm, empty_groups):
    """The grid's tables (prefetched scalars): where each group's rows
    start (``offsets`` [G + 1]), and for each visit, in order, its group
    and its row tile ([m / tm + G - 1], the most visits there can be: a
    tile is visited once, and once more for every further group that
    starts in it), with the count of visits, which is data. A group with
    rows visits the tiles from that of its first row to that of its last;
    one without visits none, or with ``empty_groups`` the tile its rows
    would start in, once, for the zero matrix it is owed."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1,
                      int(empty_groups))
    length = m // tm + groups - 1
    group_ids = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), tiles,
                           total_repeat_length=length)
    before = jnp.cumsum(tiles) - tiles
    tile_ids = jnp.clip(
        first[group_ids] + jnp.arange(length, dtype=jnp.int32)
        - before[group_ids], 0, m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group_ids, tile_ids), jnp.sum(tiles)


def visited_work(group_sizes, m, plan):
    """(strips multiplied, strips in the tiles visited) of one column
    tile's walk over ``m`` rows in groups of ``group_sizes`` (concrete),
    by the rule of ``_visits`` and the kernels' walk: a visit multiplies
    the strips that hold a row of its group, where a kernel that takes
    the tile whole multiplies ``tm / strip`` a visit. One less their
    ratio is the share of the visited tiles' rows that is skipped."""
    del m
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    starts, has = ends - sizes, sizes > 0
    last = np.maximum(ends - 1, 0)
    strips = np.where(has, last // plan.strip - starts // plan.strip + 1, 0)
    tiles = np.where(has, last // plan.tm - starts // plan.tm + 1, 0)
    return int(strips.sum()), int(tiles.sum()) * (plan.tm // plan.strip)


def _walk(start, end, plan, whole, strip_of):
    """A visit's work: ``whole()`` where the group's rows [start, end),
    counted from the tile's first, cover the tile, else ``strip_of(rows,
    keep)`` for every strip that holds one of them, ``rows`` the strip's
    slice of the tile and ``keep`` None for a strip inside the group,
    else a function of a width that says which entries of a [strip,
    width] block are in the group's rows."""
    tm, strip = plan.tm, plan.strip
    covered = (start <= 0) & (end >= tm)

    @pl.when(covered)
    def _():
        whole()

    def one(s, carry):
        lo = s * strip

        @pl.when((lo < end) & (lo + strip > start))
        def _():
            rows = pl.ds(pl.multiple_of(lo, strip), strip)
            inside = (lo >= start) & (lo + strip <= end)

            @pl.when(inside)
            def _():
                strip_of(rows, None)

            @pl.when(jnp.logical_not(inside))
            def _():
                def keep(width):
                    at = lo + lax.broadcasted_iota(jnp.int32, (strip, width),
                                                   0)
                    return (at >= start) & (at < end)

                strip_of(rows, keep)

        return carry

    # ``tgmm`` visits a group without rows once, for its zero matrix.
    @pl.when(jnp.logical_not(covered) & (end > start))
    def _():
        lax.fori_loop(0, tm // strip, one, None)


def _gmm_kernel(offsets_ref, group_ids_ref, tile_ids_ref, slots_ref, lhs_ref,
                rhs_hbm, out_ref, rhs_ref, arrived, *, plan, contract,
                block_of):
    """A visit of ``gmm``. The matrices stay in HBM and the kernel fetches
    a group's block [k, tn] itself, a group ahead: at a group's first
    visit its block is waited for and the next group's asked for, into
    the other of two slots (``slots_ref``: which, by visit), so that the
    fetch has the whole group's visits to arrive in and not the one visit
    before it, which a boundary may have cut to a strip. A column tile's
    walk starts with a fetch it waits for, and leaves none in flight."""
    column, visit = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    group, slot = group_ids_ref[visit], slots_ref[visit]
    first, end = offsets_ref[group], offsets_ref[group + 1]

    def fetch(group, slot):
        return pltpu.make_async_copy(
            block_of(rhs_hbm, group, column), rhs_ref.at[slot],
            arrived.at[slot])

    @pl.when(visit == 0)
    def _():
        fetch(group, slot).start()

    @pl.when((visit == 0) | (group_ids_ref[jnp.maximum(visit - 1, 0)]
                             != group))
    def _():
        fetch(group, slot).wait()
        then = visit + (end - 1) // plan.tm - first // plan.tm + 1

        @pl.when(then <= last)
        def _():
            fetch(group_ids_ref[jnp.minimum(then, last)], 1 - slot).start()

    row0 = tile_ids_ref[visit] * plan.tm

    def whole():
        def part(i, carry):
            rows = pl.ds(pl.multiple_of(i * plan.part, plan.part), plan.part)
            out_ref[rows, :] = _mxu_dot(lhs_ref[rows, :], rhs_ref[slot],
                                        contract).astype(out_ref.dtype)
            return carry

        lax.fori_loop(0, plan.tm // plan.part, part, None)

    def strip_of(rows, keep):
        product = _mxu_dot(lhs_ref[rows, :], rhs_ref[slot], contract)
        if keep is not None:
            # The other rows are other groups', written by their visits.
            product = jnp.where(keep(plan.tn), product,
                                out_ref[rows, :].astype(jnp.float32))
        out_ref[rows, :] = product.astype(out_ref.dtype)

    _walk(first - row0, end - row0, plan, whole, strip_of)


def _tgmm_kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, plan):
    visit, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ids_ref[visit]
    row0 = tile_ids_ref[visit] * plan.tm

    @pl.when((visit == 0) | (group_ids_ref[jnp.maximum(visit - 1, 0)]
                             != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(lhs, rhs):
        acc_ref[...] += _mxu_dot(lhs, rhs, ((0,), (0,)))

    def strip_of(rows, keep):
        lhs, rhs = lhs_ref[rows, :], rhs_ref[rows, :]
        if keep is not None:
            lhs, rhs = (jnp.where(keep(x.shape[1]), x.astype(jnp.float32),
                                  0.0).astype(x.dtype) for x in (lhs, rhs))
        add(lhs, rhs)

    _walk(offsets_ref[group] - row0, offsets_ref[group + 1] - row0, plan,
          lambda: add(lhs_ref[...], rhs_ref[...]), strip_of)

    @pl.when((visit == last) | (group_ids_ref[jnp.minimum(visit + 1, last)]
                                != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _call(name, kernel, tables, grid, in_specs, out_spec, out_shape, scratch,
          cost, interpret):
    """The ``pallas_call`` named ``name`` over ``grid``, ``tables``
    prefetched scalar tables before its operands."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=tables, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",) + ("arbitrary",) * (
                len(grid) - 1)),
        cost_estimate=cost,
        interpret=interpret,
        name=name,
    )


def _no_plan(kind, shape, dtype):
    return ValueError(
        f"{kind} {tuple(shape)} {jnp.dtype(dtype).name}: no tiles fit "
        f"{VMEM_BUDGET} B of VMEM (kernel_plan)")


@functools.partial(jax.jit, static_argnames=(
    "preferred_element_type", "transpose_rhs", "interpret", "plan"))
def gmm(lhs, rhs, group_sizes, preferred_element_type=None,
        transpose_rhs=False, interpret=False, plan=None):
    """[m, n]: ``lhs[rows of g] @ rhs[g]`` for consecutive groups of
    ``group_sizes`` [G] (int32) rows of ``lhs`` [m, k], ``rhs`` [G, k, n]
    or, with ``transpose_rhs``, [G, n, k]. Operands as they are on the
    MXU, float32 accumulation, one cast to ``preferred_element_type``
    (default: ``lhs``'s). Rows past the last group: the module's
    docstring. ``plan``: ``kernel_plan``'s, where the caller has one."""
    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1 if transpose_rhs else 2]
    kind = "gmm_t" if transpose_rhs else "gmm"
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    plan = plan or kernel_plan(m, k, n, groups, lhs.dtype, kind, out_dtype)
    if plan is None:
        raise _no_plan(kind, (m, k, n), lhs.dtype)
    _log_plan(kind, (m, k, n), lhs.dtype, plan)
    tables, visits = _visits(group_sizes, m, plan.tm, False)
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=(n // plan.tn * m * k + groups * k * n)
        * lhs.dtype.itemsize + m * n * out_dtype.itemsize)
    contract = ((1,), (1 if transpose_rhs else 0,))
    # Which of the two slots a visit's matrix is in: its group's place
    # among the groups with rows, odd or even.
    slots = ((jnp.cumsum(group_sizes > 0) - 1)[tables[1]] % 2).astype(
        jnp.int32)
    if transpose_rhs:
        block, block_of = (plan.tn, k), lambda ref, g, j: ref.at[
            g, pl.ds(j * plan.tn, plan.tn), :]
    else:
        block, block_of = (k, plan.tn), lambda ref, g, j: ref.at[
            g, :, pl.ds(j * plan.tn, plan.tn)]
    call = _call(
        "gmm",
        functools.partial(_gmm_kernel, plan=plan, contract=contract,
                          block_of=block_of),
        4, (n // plan.tn, visits),
        [pl.BlockSpec((plan.tm, k),
                      lambda j, v, off, gid, tid, slot: (tid[v], 0)),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((plan.tm, plan.tn),
                     lambda j, v, off, gid, tid, slot: (tid[v], j)),
        jax.ShapeDtypeStruct((m, n), out_dtype),
        [pltpu.VMEM((2, *block), rhs.dtype),
         pltpu.SemaphoreType.DMA((2,))], cost, interpret)
    return call(*tables, slots, lhs, rhs)


@functools.partial(jax.jit, static_argnames=(
    "preferred_element_type", "interpret", "plan"))
def tgmm(lhs, rhs, group_sizes, preferred_element_type=None,
         interpret=False, plan=None):
    """[G, k, n]: ``lhs[rows of g]^T @ rhs[rows of g]`` for consecutive
    groups of ``group_sizes`` [G] (int32) rows of ``lhs`` [m, k] and
    ``rhs`` [m, n], the rows summed in float32, one cast to
    ``preferred_element_type`` (default: ``lhs``'s); the zero matrix for
    a group without rows. Rows past the last group are read by no sum."""
    m, k = lhs.shape
    n = rhs.shape[1]
    groups = group_sizes.shape[0]
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    plan = plan or kernel_plan(m, k, n, groups, lhs.dtype, "tgmm", out_dtype)
    if plan is None:
        raise _no_plan("tgmm", (m, k, n), lhs.dtype)
    _log_plan("tgmm", (m, k, n), lhs.dtype, plan)
    tables, visits = _visits(group_sizes, m, plan.tm, True)
    call = _call(
        "tgmm", functools.partial(_tgmm_kernel, plan=plan), 3,
        (n // plan.tn, k // plan.tk, visits),
        [pl.BlockSpec((plan.tm, plan.tk),
                      lambda j, i, v, off, gid, tid: (tid[v], i)),
         pl.BlockSpec((plan.tm, plan.tn),
                      lambda j, i, v, off, gid, tid: (tid[v], j))],
        pl.BlockSpec((None, plan.tk, plan.tn),
                     lambda j, i, v, off, gid, tid: (gid[v], i, j)),
        jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        [pltpu.VMEM((plan.tk, plan.tn), jnp.float32)],
        pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(n // plan.tn * m * k + k // plan.tk * m * n)
            * lhs.dtype.itemsize + groups * k * n * out_dtype.itemsize),
        interpret)
    return call(*tables, lhs, rhs)
