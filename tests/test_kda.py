"""The delta-rule scan of a KDA layer (``ops/kda.py``): the chunked form
against the recurrence over time (``benchmark/reference_ling.py``'s, a
token a step in float32), output and every gradient, at several
chunk sizes and at the ends of what the gate and beta may be; the Pallas
kernel pair, interpreted, against the scan over chunks; the solve; the
plan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference_ling import delta_rule as recurrence
from horovod_tpu.common import metrics
from horovod_tpu.ops import kda


def operands(seed, b, T, H, K, V, g_range, beta_range, alike=0.0):
    """Unit keys, queries at K^-1/2, the gate and beta uniform in their
    ranges; ``alike`` mixes one vector into every key of a head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, T, H, K))
    k = jax.random.normal(ks[1], (b, T, H, K))
    k = alike * jax.random.normal(ks[5], (b, 1, H, K)) + (1 - alike) * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, T, H, V))
    g = jax.random.uniform(ks[3], (b, T, H, K), minval=g_range[0],
                           maxval=g_range[1])
    beta = jax.random.uniform(ks[4], (b, T, H), minval=beta_range[0],
                              maxval=beta_range[1])
    return q, k, v, g, beta


def both(fn, args):
    """(output, gradient by every operand of a weighted sum of it)."""
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    out, grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda o: (jnp.sum(o * weight), o))(fn(*a)),
        argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return out[1], grads


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# The gate at its floor, near zero, and across; beta at both ends; keys
# that nearly repeat, where the solve is worst conditioned.
REGIMES = {
    "middle": dict(g_range=(-1.0, -0.01), beta_range=(0.1, 0.9)),
    "gate at its floor": dict(g_range=(-5.0, -4.9), beta_range=(0.0, 1.0)),
    "gate near zero": dict(g_range=(-1e-3, 0.0), beta_range=(0.1, 0.9)),
    "gate across": dict(g_range=(-5.0, 0.0), beta_range=(0.0, 1.0)),
    "beta near zero": dict(g_range=(-0.5, 0.0), beta_range=(0.0, 1e-3)),
    "beta near one, keys alike": dict(g_range=(-1e-3, 0.0),
                                      beta_range=(0.95, 1.0), alike=0.7),
}


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_chunked_form_is_the_recurrence(regime, chunk):
    """Forward and every gradient. Where every channel decays by e^-5 a
    token the sums inside a chunk reach 5 x chunk, whose float32 spacing
    (3e-5 at 320) is the decays' relative error; where keys repeat under
    beta near one the system is ill conditioned for either form."""
    args = operands(3, 2, 160, 2, 32, 48, **REGIMES[regime])
    want, want_grads = both(recurrence, args)
    got, grads = both(lambda *a: kda.kda_chunked(*a, chunk=chunk), args)
    tol = {"gate at its floor": 1e-3, "gate across": 2e-5,
           "beta near one, keys alike": 2e-4}.get(regime, 5e-6)
    assert rel(got, want) < tol
    scale = max(float(jnp.abs(x).max()) for x in want_grads)
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        # By the leaf's own size, and for a leaf whose gradient all but
        # vanishes (the gate's at its floor) by the largest leaf's.
        assert (rel(a, b) < tol
                or float(jnp.abs(a - b).max()) < tol * scale), name


def test_a_length_the_chunk_does_not_divide_is_padded():
    args = operands(4, 1, 100, 2, 16, 16, (-1.0, 0.0), (0.0, 1.0))
    want, want_grads = both(recurrence, args)
    got, grads = both(lambda *a: kda.kda_chunked(*a, chunk=64), args)
    assert got.shape == want.shape and rel(got, want) < 5e-6
    assert max(rel(a, b) for a, b in zip(grads, want_grads)) < 5e-6


@pytest.mark.parametrize("chunk, H, heads", [
    (64, 2, 2), (128, 2, 2), (64, 3, 1), (128, 4, 4), (64, 6, 2)])
def test_the_kernel_pair_interpreted_is_the_scan_over_chunks(monkeypatch,
                                                             chunk, H, heads):
    """At heads of one lane tile, which the plan takes, one, two and four
    of them a grid step: the same chunk mathematics under the other
    driver, the operands read where they lie in [b, T, H K], a step's
    heads neighbouring lane tiles, the chunks turned round for the
    backward walk."""
    args = operands(5, 1, 256, H, 128, 128, (-5.0, 0.0), (0.0, 1.0))
    assert kda.kernel_plan(H, 128, 128, chunk, jnp.float32).heads == heads
    monkeypatch.delenv("HVD_PALLAS_INTERPRET", raising=False)
    want, want_grads = both(lambda *a: kda.kda_chunked(*a, chunk=chunk),
                            args)
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    before = metrics.counters()
    got, grads = both(lambda *a: kda.kda_chunked(*a, chunk=chunk), args)
    after = metrics.counters()
    for kind in ("fwd", "bwd"):
        traced = (after.get(f"kernels.traced.kda_{kind}", 0)
                  - before.get(f"kernels.traced.kda_{kind}", 0))
        assert traced > 0
        name = f"kernels.kda_{kind}.heads_per_step"
        assert after.get(name, 0) - before.get(name, 0) == traced * heads
    assert rel(got, want) < 1e-6
    assert max(rel(a, b) for a, b in zip(grads, want_grads)) < 1e-6
    # And both are the recurrence.
    assert rel(got, recurrence(*args)) < 2e-5


def test_the_kernels_reach_the_chunk_mathematics_through_the_module(
        monkeypatch):
    """``benchmark/limit_check_ling.py`` rounds the state a chunk hands on
    by patching ``kda._chunk_forward`` with a wrapper of positional
    arguments: the kernels look the function up when they are traced and
    pass nothing by name."""
    args = operands(8, 1, 128, 2, 128, 128, (-1.0, 0.0), (0.0, 1.0))
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    want = kda.kda_chunked(*args, chunk=64)
    forward, calls = kda._chunk_forward, []

    def rounded(*xs):
        calls.append(len(xs))
        o, state = forward(*xs)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(kda, "_chunk_forward", rounded)
    got = kda.kda_chunked(*args, chunk=64)
    assert calls == [7]
    assert 1e-4 < rel(got, want) < 1e-2


def test_bf16_operands_stay_near_the_float32_recurrence():
    args = operands(6, 1, 128, 2, 32, 32, (-2.0, 0.0), (0.0, 1.0))
    cast = [x.astype(jnp.bfloat16) for x in args[:3]] + list(args[3:])
    got = kda.kda_chunked(*cast, chunk=64)
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), recurrence(*args)) < 2e-2


@pytest.mark.parametrize("C", [16, 64, 128])
def test_the_solve_is_the_inverse(C):
    """Of a random strictly lower-triangular matrix, and of the one whose
    whole-chunk power series would overflow: every entry one (keys that
    repeat under beta one), whose inverse is the two-diagonal matrix."""
    lower = np.tril(np.ones((C, C), np.float32), -1)
    A = lower * np.asarray(jax.random.normal(jax.random.PRNGKey(C), (C, C)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(kda._unit_lower_inverse(jnp.asarray(A), kda.SUB))
        ones = np.asarray(kda._unit_lower_inverse(jnp.asarray(lower),
                                                  kda.SUB))
    want = np.linalg.inv(np.eye(C) + A.astype(np.float64))
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()
    np.testing.assert_allclose(ones, np.eye(C) - np.eye(C, k=-1), atol=1e-4)


@pytest.mark.parametrize("shape, taken", [
    ((32, 128, 128, 64), True), ((32, 128, 128, 128), True),
    ((32, 128, 128, 256), True), ((4, 64, 128, 64), False),
    ((4, 128, 96, 64), False), ((4, 128, 128, 32), False),
    ((4, 128, 128, 72), False)])
def test_the_plan_takes_lane_tiles_and_whole_blocks(shape, taken):
    plan = kda.kernel_plan(*shape, jnp.bfloat16)
    assert (plan is not None) == taken
    if taken:
        assert (plan.chunk, plan.sub) == (shape[3], kda.SUB)
        assert plan.vmem_bytes <= 40 << 20
        assert kda.kernel_plan(*shape, jnp.bfloat16, kind="fwd").vmem_bytes \
            <= plan.vmem_bytes


@pytest.mark.parametrize("shape, dtype, heads", [
    ((32, 128, 128, 128), jnp.bfloat16, kda._HEADS[0]),   # the cell's
    ((3, 128, 128, 128), jnp.bfloat16, 1),
    ((6, 128, 128, 128), jnp.bfloat16, 2),
    ((5, 128, 128, 64), jnp.float32, 1),
    # What the counted VMEM refuses: four heads' chunks of 256 tokens are
    # 50 MB of the 40 backward, two fit; in float32, or of 384 tokens, only
    # one head's do.
    ((32, 128, 128, 256), jnp.bfloat16, 2),
    ((32, 128, 128, 256), jnp.float32, 1),
    ((32, 128, 128, 384), jnp.bfloat16, 1)])
def test_the_plan_carries_the_heads_that_divide_and_fit(shape, dtype, heads):
    """The most of four, two and one that divides the heads and whose
    chunks fit the VMEM budget together (``heads`` is the backward pass's,
    which counts more a head than the forward's)."""
    plans = {}
    for kind in ("fwd", "bwd"):
        plan = plans[kind] = kda.kernel_plan(*shape, dtype, kind=kind)
        one = kda.kernel_plan(1, *shape[1:], dtype, kind=kind)
        assert one.heads == 1
        assert plan.vmem_bytes == plan.heads * one.vmem_bytes <= 40 << 20
        assert shape[0] % plan.heads == 0
    assert plans["fwd"].heads >= plans["bwd"].heads == heads


def test_a_shape_the_plan_refuses_runs_as_the_scan(monkeypatch):
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    args = operands(7, 1, 64, 2, 16, 16, (-1.0, 0.0), (0.0, 1.0))
    before = metrics.counters().get("kernels.traced.kda_fwd", 0)
    got = kda.kda_chunked(*args, chunk=32)
    assert metrics.counters().get(
        "kernels.traced.kda_fwd", 0) == before
    assert rel(got, recurrence(*args)) < 5e-6
