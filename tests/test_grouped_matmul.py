"""``ops/grouped_matmul.py``: the Pallas grouped matmuls, interpreted,
against ``lax.ragged_dot`` and its two gradients; the strips the kernels
multiply against hand-counted cases; the plans' VMEM at the four expert
cells' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops import grouped_matmul as gm

# (rows, group sizes, groups of the stack before the layer's): tiles of
# 512 rows, strips of 128.
CASES = {
    "on_tile_edges": (2048, [512, 1024, 512], 0),
    "on_strip_edges": (1024, [128, 384, 256, 256], 0),
    "off_every_edge": (1024, [100, 300, 451, 173], 0),
    "inside_one_strip": (1024, [130, 20, 50, 40, 784], 0),
    "one_row_and_empty_first": (1024, [0, 1, 600, 423], 0),
    "one_row_and_empty_middle": (1024, [511, 0, 1, 0, 1, 511], 0),
    "one_row_and_empty_last": (1024, [700, 323, 1, 0], 0),
    "one_group_many_tiles": (2048, [0, 2048, 0], 0),
    "rows_past_the_last_group": (2048, [300, 90, 513], 0),
    "a_layer_inside_a_stack": (1024, [200, 0, 568, 256], 8),
    "a_short_tile": (192, [5, 40, 0, 19, 100], 0),
}


def _operands(m, k, n, groups, dtype, seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), dtype),
            jnp.asarray(rng.randn(groups, k, n) * k ** -0.5, dtype),
            jnp.asarray(rng.randn(m, n), dtype))


def _close(got, want, dtype):
    # One rounding of a float32 sum to the result's type apart, or the
    # order of a float32 sum over a thousand rows.
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", gm.KINDS)
@pytest.mark.parametrize("case", CASES)
def test_kernels_agree_with_ragged_dot_and_its_gradients(case, kind, dtype):
    m, sizes, before = CASES[case]
    k, n = 256, 128
    groups = len(sizes)
    lhs, rhs, grad = _operands(m, k, n, groups, dtype, len(case))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    covered = sum(sizes)
    # What no group covers gives nothing to any sum.
    grad = jnp.where(jnp.arange(m)[:, None] < covered, grad, 0)
    f32 = [x.astype(jnp.float32) for x in (lhs, rhs, grad)]
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(
            lambda a, b: lax.ragged_dot(a, b, group_sizes), *f32[:2])
        want_lhs, want_rhs = pull(f32[2])
    # The layer's groups where they lie in a stack of three layers'.
    stack = jnp.concatenate([jnp.full((before, k, n), jnp.nan, dtype), rhs,
                             jnp.full((2 * groups - before, k, n), jnp.nan,
                                      dtype)]) if before else rhs
    stacked_sizes = jnp.zeros(len(stack), jnp.int32).at[
        before:before + groups].set(group_sizes)
    if kind == "gmm":
        got = gm.gmm(lhs, stack, stacked_sizes, interpret=True)
        assert got.shape == (m, n) and got.dtype == dtype
        _close(got[:covered], want[:covered], dtype)
    elif kind == "gmm_t":
        got = gm.gmm(grad, stack, stacked_sizes, transpose_rhs=True,
                     interpret=True)
        assert got.shape == (m, k) and got.dtype == dtype
        _close(got[:covered], want_lhs[:covered], dtype)
    else:
        # Rows past the last group may hold anything, in either operand.
        junk = jnp.where(jnp.arange(m)[:, None] < covered, 0, jnp.nan)
        got = gm.tgmm(lhs + junk.astype(dtype), grad + junk.astype(dtype),
                      group_sizes, interpret=True)
        assert got.shape == (groups, k, n) and got.dtype == dtype
        _close(got, want_rhs, dtype)
        for g, size in enumerate(sizes):
            if size == 0:
                assert not np.asarray(got[g], np.float32).any()


@pytest.mark.parametrize("part", [128, 256])
@pytest.mark.parametrize("kind", ["gmm", "gmm_t"])
def test_gmm_multiplies_a_tile_inside_its_group_by_parts(kind, part):
    """Where the product of a whole tile does not fit beside the blocks,
    the plan takes it ``part`` rows at a time: the same rows' products."""
    m, k, n, sizes = 2048, 256, 128, [100, 1436, 0, 512]
    lhs, rhs, grad = _operands(m, k, n, len(sizes), jnp.float32, 5)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    if kind == "gmm_t":
        lhs, k, n = grad, n, k
    plan = gm.kernel_plan(m, k, n, len(sizes), jnp.float32, kind)
    assert plan.part == plan.tm == 512
    whole, by_parts = (
        gm.gmm(lhs, rhs, group_sizes, transpose_rhs=kind == "gmm_t",
               interpret=True, plan=p)
        for p in (plan, plan._replace(part=part)))
    np.testing.assert_array_equal(whole, by_parts)


def test_tgmm_cuts_its_output_where_the_whole_matrix_does_not_fit():
    """An output block smaller than the matrix, on both sides: every
    block's walk over the visits starts from a zeroed accumulator."""
    m, k, n, sizes = 1024, 256, 256, [100, 0, 611, 313]
    lhs, _, grad = _operands(m, k, n, len(sizes), jnp.float32, 3)
    plan = gm.kernel_plan(m, k, n, len(sizes), jnp.float32, "tgmm")
    assert (plan.tk, plan.tn) == (k, n)
    got = gm.tgmm(lhs, grad, jnp.asarray(sizes, jnp.int32), interpret=True,
                  plan=plan._replace(tk=128, tn=128))
    ends = np.cumsum(sizes)
    for g, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        np.testing.assert_allclose(got[g], lhs[lo:hi].T @ grad[lo:hi],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sizes, m, strips, of", [
    # Every boundary on a tile edge: nothing to skip.
    ([512, 1024, 512], 2048, 16, 16),
    # One group of every row.
    ([2048], 2048, 16, 16),
    # 100 | 300 | 451 | 173: the first tile is visited by three groups
    # (1 + 4 + 1 strips of its 4), the second by two (3 + 2).
    ([100, 300, 451, 173], 1024, 1 + 4 + (1 + 3) + 2, 5 * 4),
    # A group inside one strip costs one strip, not a tile; empty groups
    # cost nothing.
    ([130, 20, 0, 50], 512, 2 + 1 + 1, 3 * 4),
    # Rows past the last group: their tiles are not visited.
    ([10], 4096, 1, 4),
    # One row at the end of a tile, one at the start of the next.
    ([511, 1, 1, 511], 1024, 4 + 1 + 1 + 4, 4 * 4),
])
def test_visited_work_counts_the_strips_of_hand_counted_cases(
        sizes, m, strips, of):
    plan = gm.kernel_plan(m, 256, 128, len(sizes), jnp.bfloat16)
    assert (plan.tm, plan.strip) == (512, 128)
    assert gm.visited_work(sizes, m, plan) == (strips, of)
    # The grid's own tables say the same visits.
    _, visits = gm._visits(jnp.asarray(sizes, jnp.int32), m, plan.tm, False)
    assert int(visits) * (plan.tm // plan.strip) == of


def test_visits_name_every_tile_a_group_touches_in_order():
    sizes = jnp.asarray([100, 0, 924, 0, 1024], jnp.int32)
    (offsets, groups, tiles), visits = gm._visits(sizes, 2048, 512, False)
    assert offsets.tolist() == [0, 100, 100, 1024, 1024, 2048]
    assert int(visits) == 5
    assert groups[:5].tolist() == [0, 2, 2, 4, 4]
    assert tiles[:5].tolist() == [0, 0, 1, 2, 3]
    # tgmm owes an empty group its zero matrix: one visit each.
    (_, groups, tiles), visits = gm._visits(sizes, 2048, 512, True)
    assert int(visits) == 7
    assert groups[:7].tolist() == [0, 1, 2, 2, 3, 4, 4]
    assert tiles[:7].tolist() == [0, 0, 0, 1, 2, 2, 3]
    assert len(groups) == len(tiles) == 2048 // 512 + 5 - 1


@pytest.mark.parametrize("cell", ["olmoe", "zaya", "trinity", "glm"])
def test_plans_at_the_cells_shapes_count_their_vmem_under_the_budget(cell):
    from tools.pallas_bench import GMM_CELLS  # the four cells' shapes

    m, d, f, groups, layers = (GMM_CELLS[cell][key] for key in (
        "m", "d", "f", "groups", "layers"))
    products = [(kind, k, n) for kind in gm.KINDS
                for k, n in ((d, f), (f, d))]
    products.append(("tgmm", 256, d))  # the sums over a token's rows
    for kind, k, n in products:
        plan = gm.kernel_plan(m, k, n, layers * groups, jnp.bfloat16, kind)
        assert plan is not None, (kind, k, n)
        assert plan.vmem_bytes <= gm.VMEM_BUDGET
        assert (plan.tm, plan.strip) == (gm.ROW_TILE, 128)
        assert plan.tm % plan.part == 0 and plan.part % plan.strip == 0
        assert n % plan.tn == 0 and plan.tn % 128 == 0
        if kind == "tgmm":
            assert k % plan.tk == 0 and plan.tk % 128 == 0
        else:
            # The contraction whole: a group's matrix is fetched once.
            assert plan.tk == k
        # The count is of these tiles.
        assert plan.vmem_bytes == gm._vmem_bytes(
            kind, plan.tm, plan.part, plan.tk, plan.tn, 2, 2)


def test_a_contraction_too_long_for_vmem_has_no_plan():
    assert gm.kernel_plan(1024, 1 << 20, 128, 4, jnp.float32) is None
    with pytest.raises(ValueError, match="no tiles fit"):
        jax.eval_shape(
            lambda a, b, s: gm.gmm(a, b, s),
            jax.ShapeDtypeStruct((1024, 1 << 20), jnp.float32),
            jax.ShapeDtypeStruct((4, 1 << 20, 128), jnp.float32),
            jax.ShapeDtypeStruct((4,), jnp.int32))
