"""The Mamba-2 scan's kernel pair (``ops/ssd.py``: ``ssd_fwd``,
``ssd_bwd``) interpreted on the CPU under ``HVD_PALLAS_INTERPRET=1``, at
the smallest sizes the plan takes (a chunk and a state of 128): against
the einsum form it replaces and against the benchmark's recurrence over
time (``reference_hybrid.ssd_recurrence``), value and every gradient; the
plan's decisions; what falls back; what the backward pass keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_hybrid
from horovod_tpu.ops import pallas_attention, ssd

NAMES = "x dt A B C D".split()


def _inputs(T, H, P, N=128, dtype=jnp.float32, seed=0):
    """One sequence with decays well under one (``exp(dt A)`` between 0.2
    and 0.9 a token), so that a sum one token off, a missing carried
    state or a gradient without its decay moves every number."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (1, T, H, P)).astype(dtype)
    Bm = (jax.random.normal(ks[1], (1, T, N)) / N ** 0.5).astype(dtype)
    Cm = jax.random.normal(ks[2], (1, T, N)).astype(dtype)
    dt = jax.random.uniform(ks[3], (1, T, H), jnp.float32, 0.2, 1.0)
    A = -jax.random.uniform(ks[4], (H,), jnp.float32, 0.5, 1.6)
    D = jax.random.normal(ks[5], (H,))
    weight = jax.random.normal(ks[6], x.shape)
    return (x, dt, A, Bm, Cm, D), weight


def _value_and_grads(fn, args, weight):
    y = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
                     argnums=range(6))(*args)
    return y, grads


def _recurrence(x, dt, A, Bm, Cm, D):
    f32 = lambda a: a.astype(jnp.float32)
    return reference_hybrid.ssd_recurrence(f32(x[0]), dt[0], A, f32(Bm[0]),
                                           f32(Cm[0]), D)[None]


def _both(monkeypatch, args, weight, chunk):
    """(the kernel path's value and gradients, the einsum form's)."""
    def scan():  # a new function a trace: jax keeps a function's trace
        return lambda *a: ssd.ssd_chunked(*a, chunk=chunk)

    monkeypatch.delenv("HVD_PALLAS_INTERPRET", raising=False)
    assert "pallas_call" not in str(jax.make_jaxpr(scan())(*args))
    einsums = _value_and_grads(scan(), args, weight)
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    assert str(jax.make_jaxpr(scan())(*args)).count("pallas_call") == 1
    return _value_and_grads(scan(), args, weight), einsums


def _grid_steps(args, chunk):
    """Head groups a chunk that the backward kernel walks."""
    (_, _, H, P), N = args[0].shape, args[3].shape[-1]
    return H // ssd.kernel_plan(H, P, N, chunk, args[0].dtype).heads


# T, H, P, chunk, VMEM budget (None: the module's), head groups a chunk.
CASES = {
    "2.3-chunks-padded": (300, 4, 64, 128, None, 1),
    "two-strips-a-tile": (512, 2, 64, 256, None, 1),
    "a-tp-members-two-heads": (256, 2, 64, 128, None, 1),
    "heads-of-128-one-a-body": (256, 2, 128, 128, None, 1),
    "two-head-groups-a-chunk": (256, 16, 64, 128, 6 << 20, 2),
}


@pytest.mark.parametrize("case", CASES)
def test_kernels_are_the_einsum_form_and_the_recurrence_in_float32(
        monkeypatch, case):
    T, H, P, chunk, budget, groups = CASES[case]
    if budget:
        monkeypatch.setattr(pallas_attention, "VMEM_BUDGET", budget)
    args, weight = _inputs(T, H, P)
    assert _grid_steps(args, chunk) == groups
    (y, grads), (y_e, grads_e) = _both(monkeypatch, args, weight, chunk)
    y_r, grads_r = _value_and_grads(_recurrence, args, weight)
    for other in (y_e, y_r):
        np.testing.assert_allclose(y, other, rtol=2e-5, atol=2e-5)
    for name, g, g_e, g_r in zip(NAMES, grads, grads_e, grads_r):
        for what, other in (("einsum form", g_e), ("recurrence", g_r)):
            np.testing.assert_allclose(
                g, other, rtol=1e-4, atol=2e-5 * float(jnp.abs(other).max()),
                err_msg=f"gradient by {name} against the {what}")


@pytest.mark.parametrize("case", ["2.3-chunks-padded", "two-strips-a-tile"])
def test_kernels_in_bf16_round_where_the_einsum_form_rounds(monkeypatch,
                                                            case):
    """bf16 ``x``, ``B``, ``C`` with float32 ``dt``, decays and sums: the
    value is the einsum form's to a bf16 unit in the last place (both cast
    the float32 tile once, before the matmul), within 1 % of the float32
    recurrence on the same rounded inputs in relative L2; every gradient
    within 1 % of the einsum form's largest entry (that form rounds the
    tile's gradient to bf16, the kernel keeps it float32) and 2 % of the
    recurrence's in relative L2."""
    T, H, P, chunk, _, _ = CASES[case]
    args, weight = _inputs(T, H, P, dtype=jnp.bfloat16)
    (y, grads), (y_e, grads_e) = _both(monkeypatch, args, weight, chunk)
    assert y.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)
    ulp = 2.0 ** -7 * np.abs(f32(y_e)) + 1e-3
    assert (np.abs(f32(y) - f32(y_e)) <= ulp).all()
    y_r, grads_r = _value_and_grads(_recurrence, args, weight)
    assert np.linalg.norm(f32(y) - y_r) / np.linalg.norm(y_r) < 1e-2
    for name, g, g_e, g_r in zip(NAMES, grads, grads_e, grads_r):
        assert g.dtype == g_e.dtype, name
        assert np.abs(f32(g) - f32(g_e)).max() < 1e-2 * np.abs(
            f32(g_e)).max(), name
        assert np.linalg.norm(f32(g) - f32(g_r)) < 2e-2 * np.linalg.norm(
            f32(g_r)), name


@pytest.mark.parametrize("H, P, N, chunk, why", [
    (3, 4, 5, 8, "nothing on the lane grid"),
    (4, 64, 128, 64, "a chunk under 128 lanes"),
    (4, 64, 128, 136, "a chunk that 128 does not divide"),
    (4, 64, 64, 128, "a state under 128 lanes"),
    (4, 16, 128, 128, "eight heads to a slab"),
    (4, 96, 128, 128, "heads that fill no slab"),
    (3, 64, 128, 128, "a head without its slab's other"),
    (12, 64, 128, 128, "heads that tiles of eight do not divide"),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_a_shape_the_plan_refuses_takes_the_einsum_form(monkeypatch, H, P, N,
                                                        chunk, why):
    assert ssd.kernel_plan(H, P, N, chunk, jnp.float32) is None, why
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    args, weight = _inputs(2 * chunk + 3, H, P, N)
    scan = lambda *a: ssd.ssd_chunked(*a, chunk=chunk)
    assert "pallas_call" not in str(jax.make_jaxpr(scan)(*args))
    y, grads = _value_and_grads(scan, args, weight)
    y_r, grads_r = _value_and_grads(_recurrence, args, weight)
    np.testing.assert_allclose(y, y_r, rtol=2e-5, atol=2e-5)
    for name, g, g_r in zip(NAMES, grads, grads_r):
        np.testing.assert_allclose(
            g, g_r, rtol=1e-4, atol=2e-5 * float(jnp.abs(g_r).max()),
            err_msg=f"gradient by {name}")


def test_no_budget_for_one_slab_takes_the_einsum_form(monkeypatch):
    monkeypatch.setattr(pallas_attention, "VMEM_BUDGET", 1 << 20)
    assert ssd.kernel_plan(4, 64, 128, 128, jnp.float32) is None


@pytest.mark.parametrize("H, dtype, kind, want", [
    # granite-h-t8192: all 64 heads a grid step, two a loop body, the
    # [256, 256] tile as two strips of 128 rows (3 of 4 sub-tiles).
    (64, jnp.bfloat16, "fwd", (64, 2, 128, 128)),
    (64, jnp.bfloat16, "bwd", (64, 2, 128, 128)),
    (32, jnp.bfloat16, "bwd", (32, 2, 128, 128)),   # a tp 2 member's
    # The gradient check's float32 program: blocks of twice the size,
    # the backward's at 96 % of the budget.
    (64, jnp.float32, "fwd", (64, 2, 128, 128)),
    (64, jnp.float32, "bwd", (64, 2, 128, 128)),
])
def test_the_plan_at_the_cells_shape(H, dtype, kind, want):
    plan = ssd.kernel_plan(H, 64, 128, 256, dtype, kind=kind)
    assert plan[:4] == want
    assert plan.vmem_bytes <= pallas_attention.VMEM_BUDGET


def _shapes_outside_kernels(jaxpr):
    """Shapes of every value a jaxpr computes, its sub-jaxprs' included,
    the kernels' own bodies (VMEM) left out."""
    for eqn in jaxpr.eqns:
        yield from (v.aval.shape for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_outside_kernels(sub)


def test_no_heads_by_chunk_by_chunk_array_outside_the_kernels(monkeypatch):
    """Forward and backward: the decay and score arrays exist in VMEM
    only (the einsum form computes them: the rule below sees it)."""
    H, P, chunk = 4, 64, 128
    args, weight = _inputs(2 * chunk, H, P, dtype=jnp.bfloat16)

    def tiles():
        grad = jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(ssd.ssd_chunked(*a, chunk=chunk).astype(
                jnp.float32) * weight), argnums=range(6)))(*args)
        return str(grad), [s for s in set(_shapes_outside_kernels(grad.jaxpr))
                           if s[-2:] == (chunk, chunk) and H in s]

    monkeypatch.delenv("HVD_PALLAS_INTERPRET", raising=False)
    text, found = tiles()
    assert "pallas_call" not in text and found
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    text, found = tiles()
    assert "name=ssd_fwd" in text and "name=ssd_bwd" in text and not found


def test_the_backward_pass_keeps_the_scans_inputs_only(monkeypatch):
    """The residuals are the kernels' operands: nothing larger than ``x``,
    where one head's [Q, Q] tile alone would be."""
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    H, P, chunk = 4, 64, 128
    args, _ = _inputs(chunk, H, P)
    _, residuals = jax.vjp(lambda *a: ssd.ssd_chunked(*a, chunk=chunk),
                           *args)
    biggest = max(leaf.size for leaf in jax.tree_util.tree_leaves(residuals))
    assert biggest <= args[0].size < H * chunk * chunk


@pytest.mark.parametrize("axes", [dict(), dict(tp=2)], ids=["one-device",
                                                             "tp2"])
def test_the_decoder_with_the_kernels_is_the_decoder_with_the_einsums(
        monkeypatch, axes):
    """Two Mamba layers through ``make_loss_fn`` (the layer scan,
    ``shard_map``, a rematerialized layer that keeps the scan's output by
    name): loss and every gradient leaf the same whether the scan's
    chunks run as kernels or as einsums; over ``tp`` 2 a member's scan
    has half the heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import (
        TransformerConfig, init_params, make_loss_fn, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=2, d_head=16, d_ff=64, n_layers=2,
        max_seq=256, layer_types=("mamba", "mamba"), mamba_heads=4,
        mamba_d_head=64, mamba_d_state=128, mamba_chunk=128,
        norm="rmsnorm", gated_mlp=True, tie_embeddings=True,
        pos_table=False, remat=True)
    params = init_params(cfg, jax.random.PRNGKey(0), n_stages=1)
    params["m_D"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                params["m_D"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, 64)
    labels = jnp.roll(tokens, -1, axis=1)
    axes = dict(dict(dp=1, pp=1, sp=1, tp=1), **axes)
    mesh = build_parallel_mesh(
        jax.devices()[:int(np.prod(list(axes.values())))], **axes)
    data = NamedSharding(mesh, P("dp", "sp"))

    def run():
        fn = jax.value_and_grad(make_loss_fn(cfg, mesh, n_microbatches=1))
        text = str(jax.make_jaxpr(fn)(shard_params(params, cfg, mesh),
                                      tokens, labels))
        loss, grads = jax.jit(fn)(
            shard_params(params, cfg, mesh), jax.device_put(tokens, data),
            jax.device_put(labels, data))
        return text, float(loss), jax.device_get(grads)

    monkeypatch.delenv("HVD_PALLAS_INTERPRET", raising=False)
    text, want_loss, want = run()
    assert "pallas_call" not in text
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    text, loss, grads = run()
    assert "name=ssd_fwd" in text and "name=ssd_bwd" in text
    assert abs(loss - want_loss) / want_loss < 1e-6
    for name in want:
        scale = np.abs(want[name]).max()
        assert np.abs(grads[name] - want[name]).max() <= 2e-5 * scale, name
