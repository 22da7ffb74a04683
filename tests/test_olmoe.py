"""The OLMoE-style decoder (RMSNorm, QK-norm over the whole projected
vector, RoPE, dropless top-k gated SiLU experts, load-balance and z
terms) against the benchmark's plain float32 reference, at small widths
on the CPU: d 64, 8 experts, 3 a token, 2 layers, T 32, seeded."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import reference_moe
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_router_load_fn,
    shard_params)
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import build_parallel_mesh
from test_parallel import _dense_moe_oracle, _run_moe_layer as _layer

CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, d_head=16, n_layers=2, max_seq=32,
    use_moe=True, n_experts=8, d_expert=32, moe_top_k=3,
    router_aux_loss_coef=0.01, router_z_loss_coef=0.001, norm="rmsnorm",
    qk_norm=True, rope=True)
B, T = 4, 32


def _weights(cfg, seed=0, n_stages=1):
    """Seeded weights with norm scales away from one, so that a scale
    applied in the wrong place shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages=n_stages)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    for key, name in zip(keys, ("ln1", "ln2", "gq", "gk", "final_ln")):
        params[name] = 1 + 0.1 * jax.random.normal(key, params[name].shape)
    return params


def _batch(seed=1, vocab=256):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _program(cfg, params, tokens, labels, dp=1):
    mesh = build_parallel_mesh(jax.devices()[:dp], dp=dp, pp=1, sp=1, tp=1)
    data = NamedSharding(mesh, P("dp", "sp"))
    loss, grads = jax.jit(jax.value_and_grad(make_loss_fn(
        cfg, mesh, n_microbatches=1)))(
        shard_params(params, cfg, mesh), jax.device_put(tokens, data),
        jax.device_put(labels, data))
    return float(loss), jax.device_get(grads)


def _reference(cfg, params, tokens, labels):
    (loss, load), grads = jax.jit(
        lambda p, t, l: reference_moe.decoder_moe_loss_and_grad(
            p, t, l, cfg.moe_top_k, cfg.router_aux_loss_coef,
            cfg.router_z_loss_coef, cfg.norm_eps, cfg.rope_theta))(
        params, tokens, labels)
    return float(loss), np.asarray(load), grads


def _worst_leaf(got, want):
    """Largest difference over the reference's largest entry, by leaf."""
    return {k: float(np.abs(np.asarray(got[k], np.float32)
                            - np.asarray(want[k], np.float32)).max()
                     / np.abs(np.asarray(want[k], np.float32)).max())
            for k in want}


def test_loss_and_every_gradient_leaf_match_the_reference_in_float32():
    # 1e-5: both sides are float32 sums of at most a few thousand terms
    # taken in different orders (sorted rows against a loop over experts,
    # blocked attention against a dense softmax), which leaves 1e-6; a
    # matmul, a norm or the router in bf16 would leave 1e-3 and more.
    params, (tokens, labels) = _weights(CFG), _batch()
    loss, grads = _program(CFG, params, tokens, labels)
    want, _, want_grads = _reference(CFG, params, tokens, labels)
    assert loss == pytest.approx(want, rel=1e-5)
    worst = _worst_leaf(grads, want_grads)
    assert set(worst) == set(params)
    assert max(worst.values()) < 1e-5, worst


def test_bf16_parameters_stay_bf16_into_the_grouped_matmuls():
    # With bf16 parameters the program is held to the float32 reference
    # on the same (bf16-valued) weights at bf16's own distance at this
    # size: 8 bits of mantissa through two layers, and of 384 assignments
    # a few whose third and fourth expert change places under that
    # rounding, leave 2 to 24 % of a leaf's largest entry (read over five
    # seeds; 11 % at this one, the loss within 1e-3); a wrong formula,
    # scale or gate is off by half and more.
    # No tolerance can tell float32 experts on bf16 weights from bf16
    # ones, since float32 is only closer; so the operands are checked
    # where they enter: every grouped matmul of the loss and its gradient
    # takes bf16 on both sides and gives bf16 (float32 accumulation is
    # the MXU's own). An `.astype(float32)` on the experts fails this.
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params, (tokens, labels) = _weights(cfg), _batch()
    loss, grads = _program(cfg, params, tokens, labels)
    want, _, want_grads = _reference(cfg, params, tokens, labels)
    assert loss == pytest.approx(want, rel=5e-3)
    worst = _worst_leaf(grads, want_grads)
    assert max(worst.values()) < 0.25, worst

    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    jaxpr = jax.make_jaxpr(jax.grad(make_loss_fn(cfg, mesh, 1)))(
        params, tokens, labels)
    grouped = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                grouped.append([v.aval.dtype for v in eqn.invars[:2]]
                               + [eqn.outvars[0].aval.dtype])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    # Forward three a layer, and for each the gradient by its rows and by
    # its weights; the two layers are one scan body.
    assert len(grouped) >= 9, grouped
    assert all(dt == jnp.bfloat16 for eqn in grouped for dt in eqn), grouped


def _expert(x, params, e):
    return (jax.nn.silu(x @ params["wg"][e]) * (x @ params["wu"][e])
            ) @ params["wd"][e]


def test_nothing_is_dropped_when_every_token_picks_one_expert():
    # A static capacity of 1.25 x tokens / experts would keep 80 of these
    # 256 tokens. Here all reach expert 5 and come back with its output.
    d, f, E = 16, 32, 8
    params = moe.init_moe_params(jax.random.PRNGKey(0), d, f, E)
    router = np.zeros((d, E), np.float32)
    router[:, 5] = 4.0
    params["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 128, d))) + 0.5
    y, stats = _layer(x, params, top_k=1)
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  [0, 0, 0, 0, 0, 256, 0, 0])
    p5 = jax.nn.softmax(x @ params["router"], -1)[..., 5:6]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(p5 * _expert(x, params, 5)),
                               rtol=1e-5, atol=1e-6)


def test_gates_are_the_router_probabilities_not_renormalised():
    # norm_topk_prob false: a token's three weights sum to less than one.
    # With a division by their sum patched in, the output would be the
    # second array below, 1.5 to 3 times larger.
    d, f, E, k = 16, 32, 8, 3
    params = moe.init_moe_params(jax.random.PRNGKey(2), d, f, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, d))
    flat = np.asarray(x).reshape(-1, d)
    raw = _dense_moe_oracle(flat, params, k).reshape(x.shape)
    renormalised = _dense_moe_oracle(flat, params, k,
                                     renormalize=True).reshape(x.shape)
    y = np.asarray(_layer(x, params, top_k=k)[0])
    np.testing.assert_allclose(y, raw, rtol=1e-4, atol=1e-6)
    assert np.abs(y - renormalised).max() > 0.3 * np.abs(raw).max()
    # The field gives the other published convention, by the same code.
    y = np.asarray(_layer(x, params, top_k=k, norm_topk_prob=True)[0])
    np.testing.assert_allclose(y, renormalised, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("tp", [1, 2])
def test_qk_norm_is_over_the_whole_projected_vector(tp):
    # Heads of very different size: normalised one head at a time each
    # would come out at unit size; over the whole vector they keep their
    # ratios. The heads may be split over tp: the mean still spans all.
    b, t, H, Dh = 2, 8, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(4), (b, t, H, Dh)) \
        * jnp.asarray([0.1, 1.0, 3.0, 10.0])[:, None]
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (H, Dh))
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    got = jax.jit(jax.shard_map(
        lambda x, g: transformer._qk_norm(x, g, 1e-5), mesh=mesh,
        in_specs=(P(None, None, "tp"), P("tp")),
        out_specs=P(None, None, "tp"), check_vma=False))(x, scale)
    whole = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), (2, 3), keepdims=True) + 1e-5) * scale
    per_head = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), 3, keepdims=True) + 1e-5) * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(got - per_head).max()) > 1.0


@pytest.mark.parametrize("E,k", [(8, 3), (64, 8)])
def test_load_balance_term_is_top_k_at_forced_uniform_routing(E, k):
    # A zero router gives every expert probability 1 / E; the ties send
    # every token to the first k experts (f = 1 there), and E * sum_e
    # f_e P_e = k: 8 for OLMoE's 8 of 64. The z term is log(E)^2.
    d, f = 16, 8
    params = moe.init_moe_params(jax.random.PRNGKey(6), d, f, E)
    params["router"] = jnp.zeros((d, E), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, d))
    _, stats = _layer(x, params, top_k=k)
    assert float(stats["lb"]) == pytest.approx(k, rel=1e-6)
    assert float(stats["z"]) == pytest.approx(np.log(E) ** 2, rel=1e-5)
    assert int(np.asarray(stats["load"]).sum()) == k * 32


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_members_give_what_one_member_gives(ep):
    # One dispatch path: at ep > 1 each member runs the same code on its
    # own experts over the gathered tokens. Loss and every gradient leaf
    # equal ep 1's (float32; the order of a few sums differs).
    params, (tokens, labels) = _weights(CFG), _batch()
    loss, grads = _program(CFG, params, tokens, labels, dp=ep)
    want, want_grads = _program(CFG, params, tokens, labels, dp=1)
    assert loss == pytest.approx(want, rel=1e-6)
    worst = _worst_leaf(grads, want_grads)
    assert max(worst.values()) < 1e-5, worst


def test_the_two_router_terms_enter_the_loss_with_their_coefficients():
    params, (tokens, labels) = _weights(CFG), _batch()
    plain = dataclasses.replace(CFG, router_aux_loss_coef=0.0,
                                router_z_loss_coef=0.0)
    base, _ = _program(plain, params, tokens, labels)
    both, _ = _program(CFG, params, tokens, labels)
    want_base, _, _ = _reference(plain, params, tokens, labels)
    want_both, _, _ = _reference(CFG, params, tokens, labels)
    assert base == pytest.approx(want_base, rel=1e-5)
    # The terms themselves, from the difference (lb is at least top_k).
    assert both - base == pytest.approx(want_both - want_base, rel=1e-3)
    assert both - base > 0.01 * CFG.moe_top_k


def test_router_load_counts_every_assignment_by_layer():
    params, (tokens, _) = _weights(CFG), _batch()
    mesh = build_parallel_mesh(jax.devices()[:2], dp=2, pp=1, sp=1, tp=1)
    load = np.asarray(make_router_load_fn(CFG, mesh, n_microbatches=1)(
        shard_params(params, CFG, mesh),
        jax.device_put(tokens, NamedSharding(mesh, P("dp", "sp")))))
    _, want, _ = _reference(CFG, params, tokens, jnp.roll(tokens, -1, 1))
    assert load.shape == (CFG.n_layers, CFG.n_experts)
    np.testing.assert_array_equal(load.sum(axis=1),
                                  [CFG.moe_top_k * B * T] * CFG.n_layers)
    np.testing.assert_array_equal(load, want)


def test_the_pallas_grouped_matmul_gives_what_ragged_dot_gives(monkeypatch):
    # On the chip the three matmuls are jax's Pallas grouped matmul under
    # the scope moe_gmm; here it runs interpreted, members' trailing rows
    # and all (ep 2).
    params, (tokens, labels) = _weights(CFG), _batch()
    want, want_grads = _program(CFG, params, tokens, labels, dp=2)
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    loss, grads = _program(CFG, params, tokens, labels, dp=2)
    assert loss == pytest.approx(want, rel=1e-6)
    worst = _worst_leaf(grads, want_grads)
    assert max(worst.values()) < 1e-5, worst


# ---- the stage's stacked expert matrices, read in place ---------------------

def _slices_only(monkeypatch):
    """The grouped matmuls take their layer's slice, as if the stage had
    handed them no stack: the path the in-place reading is held to."""
    in_place = moe._grouped_matmul
    monkeypatch.setattr(
        moe, "_grouped_matmul",
        lambda lhs, rhs, group_sizes, stack=None, layer=0:
        in_place(lhs, rhs, group_sizes))


def _starved(params, expert=5):
    """No token picks ``expert`` in either layer: it shares its router
    column with experts 0, 1 and 2, ties go to the lower index, and a
    token has three picks. The others are chosen as the data has it."""
    router = np.array(params["router"])
    for twin in (1, 2, expert):
        router[..., twin] = router[..., 0]
    return dict(params, router=jnp.asarray(router))


@pytest.mark.parametrize("layer", [0, 1])
def test_a_grouped_matmul_reads_its_layer_of_the_stack(monkeypatch, layer):
    # Bit for bit what the kernels give on the slice, and its gradients:
    # the rows' through the stack again, the weights' [g, k, n] from this
    # layer's groups alone, one of them empty.
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    m, k, n, g = 64, 16, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    lhs = jax.random.normal(keys[0], (m, k))
    stack = jax.random.normal(keys[1], (2, g, k, n))
    weight = jax.random.normal(keys[2], (m, n))
    sizes = jnp.asarray([24, 0, 30, 10], jnp.int32)

    def product(lhs, rhs, *where):
        return jnp.sum(moe._grouped_matmul(lhs, rhs, sizes, *where) * weight)

    got = jax.value_and_grad(product, (0, 1))(lhs, stack[layer], stack,
                                               jnp.int32(layer))
    want = jax.value_and_grad(product, (0, 1))(lhs, stack[layer])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ragged = jax.lax.ragged_dot(lhs, stack[layer], sizes)
    np.testing.assert_allclose(float(got[0]), float(jnp.sum(ragged * weight)),
                               rtol=1e-5)
    assert got[1][1].shape == (g, k, n)
    assert not np.asarray(got[1][1][1]).any()  # the empty group's


@pytest.mark.parametrize("ep", [1, 2])
def test_stacked_experts_read_in_place_give_the_slices_values(monkeypatch,
                                                              ep):
    # Two layers in one scan: the kernels read both out of the stage's
    # stacks by the layer's index. Loss and every gradient leaf, both
    # layers of it, are those of the slice-taking path to the bit.
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    params, (tokens, labels) = _starved(_weights(CFG)), _batch()
    mesh = build_parallel_mesh(jax.devices()[:ep], dp=ep, pp=1, sp=1, tp=1)
    load = np.asarray(make_router_load_fn(CFG, mesh, n_microbatches=1)(
        shard_params(params, CFG, mesh),
        jax.device_put(tokens, NamedSharding(mesh, P("dp", "sp")))))
    assert (load[:, 5] == 0).all() and (load.sum(1) == 3 * B * T).all()
    loss, grads = _program(CFG, params, tokens, labels, dp=ep)
    with monkeypatch.context() as patch:
        _slices_only(patch)
        want, want_grads = _program(CFG, params, tokens, labels, dp=ep)
    assert loss == want
    assert set(grads) == set(params)
    for name in params:
        np.testing.assert_array_equal(grads[name], want_grads[name],
                                      err_msg=name)
    for name in ("wg", "wu", "wd"):
        assert grads[name].shape == params[name].shape
        assert not grads[name][0, :, 5].any()  # the expert no token chose
        assert grads[name][0, 0].any() and grads[name][0, 1].any()


def _walk(jaxpr, visit):
    for eqn in jaxpr.eqns:
        visit(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _walk(sub, visit)


def test_no_slice_feeds_an_expert_kernel_and_the_stack_gets_no_cotangent(
        monkeypatch):
    # The gradient's program: every kernel that needs a layer's matrices
    # takes the whole [L * E, d, f] stack, a reshape of the parameter;
    # nothing else has that shape (a cotangent of the stack would: zeros
    # broadcast, or a sum carried through the layers' scan, of length L;
    # the pipeline's scan of one tick carries the stage's gradient); no
    # kernel reads an [E, d, f] slice. Handed the slices, six would.
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    params, (tokens, labels) = _weights(CFG), _batch()
    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    L, E, d, f = CFG.n_layers, CFG.n_experts, CFG.d_model, CFG.d_expert
    stack, layer = {(L * E, d, f), (L * E, f, d)}, {(E, d, f), (E, f, d)}

    def kernels_and_stack_shaped():
        taking = {"stack": 0, "slice": 0}
        others, carried = [], []

        def visit(eqn):
            shapes = [v.aval.shape for v in eqn.invars]
            if eqn.primitive.name == "pallas_call":
                taking["stack"] += bool(stack & set(shapes))
                taking["slice"] += bool(layer & set(shapes))
            elif eqn.primitive.name != "reshape":
                others.extend(v.aval.shape for v in eqn.outvars
                              if v.aval.shape in stack)
            if eqn.primitive.name == "scan" and eqn.params["length"] == L:
                first = eqn.params["num_consts"]
                carried.extend(
                    v.aval.shape for v in
                    eqn.invars[first:first + eqn.params["num_carry"]])

        _walk(jax.make_jaxpr(jax.grad(make_loss_fn(CFG, mesh, 1)))(
            params, tokens, labels).jaxpr, visit)
        return taking, others, carried

    taking, others, carried = kernels_and_stack_shaped()
    assert taking == {"stack": 6, "slice": 0}
    assert others == []
    assert not {(L, E, d, f), (L, E, f, d)} & set(carried)
    _slices_only(monkeypatch)
    assert kernels_and_stack_shaped()[0] == {"stack": 0, "slice": 6}
