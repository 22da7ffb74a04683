"""The ZAYA1-8B block (``models/transformer.py``: the ``cca`` mixer, the
MLP router whose state crosses layers, learned residual scales, the head
and loss by blocks of tokens) at a small size on the CPU: the program
against ``benchmark/reference_zaya.py`` in loss and every leaf's gradient,
each CCA piece alone, the share tied to the model (the two halves of the
experts add up to the uncut layer, the two row slices' log-sum-exps to the
whole table's), the block-wise head against the whole one, ``tp`` 2, what
is refused, and the programs of the other configurations' tiny presets
held to the text they lowered to before this block existed."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmark import reference_zaya
from horovod_tpu.common import metrics
from horovod_tpu.common.compat import shard_map
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, block_nll, init_params, make_loss_fn, make_train_step,
    shard_params, token_nll, trained)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from test_afmoe import _eqns, _types

L, V, T, B = 3, 512, 40, 2
CFG = TransformerConfig(
    vocab=V, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, n_layers=L,
    max_seq=64, layer_types=("cca",) * L, partial_rotary_factor=0.5,
    rope_theta=5e6, pos_table=False, use_moe=True, n_experts=8,
    n_experts_held=4, first_expert_held=0, d_expert=48, moe_top_k=1,
    router_hidden=16, expert_bias_rate=1e-3, residual_scales=True,
    head_block=24, tie_embeddings=True, norm="rmsnorm", dtype=jnp.float32,
    remat=True, remat_keeps=("cca_q", "cca_kv", "cca_conv", "flash_out"))
MODEL = dict(num_hidden_layers=L, rms_norm_eps=1e-5, rope_theta=5e6,
             rotated=8, first_expert_held=0, load_balance_coeff=1e-3)


def _mesh(**axes):
    n = int(np.prod(list(axes.values()) or [1]))
    return build_parallel_mesh(jax.devices()[:n], **{
        "sp": 1, "tp": 1, "pp": 1, **axes})


def _seeded(cfg, seed=0):
    """Seeded weights with every float32 leaf that starts at a constant
    moved off it, so that no gradient is zero by symmetry."""
    params = init_params(cfg, jax.random.PRNGKey(seed), 1)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 16))
    for name in ("ln1", "ln2", "final_ln", "res1", "res2", "c_beta",
                 "r_gamma", "r_norm"):
        params[name] = params[name] + 0.1 * jax.random.normal(
            next(keys), params[name].shape)
    params["expert_bias"] = 0.02 * jax.random.normal(
        next(keys), params["expert_bias"].shape)
    return params


def _batch(seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, V)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _loss_and_grad(cfg, mesh, params, tokens, labels):
    loss_fn = make_loss_fn(cfg, mesh, 1, with_readings=True)
    sharded = shard_params(params, cfg, mesh)
    bias = {"expert_bias": sharded["expert_bias"]}
    (loss, readings), grads = jax.jit(jax.value_and_grad(
        lambda w: loss_fn({**w, **bias}, tokens, labels), has_aux=True))(
            trained(sharded))
    return loss, readings, grads


@pytest.fixture(scope="module")
def both():
    params, (tokens, labels) = _seeded(CFG), _batch()
    got = _loss_and_grad(CFG, _mesh(), params, tokens, labels)
    want = reference_zaya.loss_and_grad(params, tokens, labels, MODEL)
    readings = reference_zaya.step_readings(params, tokens, labels, MODEL)
    return got, want, readings


def test_loss_load_and_token_nll_against_the_reference(both):
    (loss, readings, _), (want, _), ref = both
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_array_equal(readings["load"], ref["load"])
    assert (np.asarray(ref["load"]).sum(1) == B * T).all()
    np.testing.assert_allclose(readings["token_nll"], ref["nll"], atol=2e-5)
    assert (np.asarray(readings["windows"]) == 1).all()


@pytest.mark.parametrize("leaf", sorted(trained(
    jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0), 1)))))
def test_gradient_of_every_leaf_against_the_reference(both, leaf):
    (_, _, grads), (_, want), _ = both
    got, want = np.asarray(grads[leaf]), np.asarray(want[leaf])
    assert np.abs(want).max() > 1e-6, "a gradient that is zero holds nothing"
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_second_half_of_the_experts_against_the_reference():
    cfg = dataclasses.replace(CFG, first_expert_held=4)
    params, (tokens, labels) = _seeded(cfg), _batch()
    loss, readings, grads = _loss_and_grad(cfg, _mesh(), params, tokens,
                                           labels)
    want, want_grads = reference_zaya.loss_and_grad(
        params, tokens, labels, dict(MODEL, first_expert_held=4))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for name in ("wg", "r_w3", "c_conv1_k"):
        np.testing.assert_allclose(
            grads[name], want_grads[name],
            atol=2e-5 * np.abs(want_grads[name]).max())


# --- each CCA piece alone

def test_depthwise_convolution_against_numpy_convolve():
    x = np.random.default_rng(0).normal(size=(1, 9, 3)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(2, 3)).astype(np.float32)
    got = transformer._causal_depthwise_conv(jnp.asarray(x), jnp.asarray(w),
                                             0.0)
    ref = reference_zaya.conv_depthwise(jnp.asarray(x)[:, :, None],
                                        jnp.asarray(w)[:, None])[:, :, 0]
    for c in range(3):
        # y_t = w[1] x_t + w[0] x_{t-1}: the full convolution's first T.
        want = np.convolve(x[0, :, c], w[::-1, c])[:9]
        np.testing.assert_allclose(got[0, :, c], want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ref[0, :, c], want, rtol=1e-5, atol=1e-6)


def test_convolution_by_head_against_a_loop_over_t():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 7, 3, 4)).astype(np.float32)
    w = rng.normal(size=(2, 3, 4, 4)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(7):
        for i in range(3):
            want[:, t, i] = x[:, t, i] @ w[1, i]
            if t:
                want[:, t, i] += x[:, t - 1, i] @ w[0, i]
    with jax.default_matmul_precision("highest"):
        got = transformer._causal_conv_by_head(jnp.asarray(x),
                                               jnp.asarray(w))
        ref = reference_zaya.conv_by_head(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref, want, rtol=1e-5, atol=1e-5)


def _mixer_inputs():
    params = _seeded(CFG)
    lp = reference_zaya.layer_leaves(params, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, CFG.d_model))
    return h, lp


def test_the_values_shift_by_one_token_in_the_upper_heads_only():
    h, lp = _mixer_inputs()
    v = reference_zaya.values(h, lp)
    plain = jnp.einsum("btd,dhk->bthk", h, lp["c_wv"])
    np.testing.assert_allclose(v[:, :, 0], plain[:, :, 0], atol=1e-5)
    np.testing.assert_array_equal(v[:, 0, 1], 0.0)  # h_{-1} = 0
    np.testing.assert_allclose(v[:, 1:, 1], plain[:, :-1, 1], atol=1e-5)


def test_the_qk_mean_with_a_group_of_two():
    """With every filter zero q and k are the mean term alone, normed:
    query head i reads key head i // 2, key head j the mean of its two
    query heads."""
    h, lp = _mixer_inputs()
    lp = {k: jnp.zeros_like(v) if "conv1" in k else v for k, v in lp.items()}
    q, k = reference_zaya.queries_keys(h, lp, dict(MODEL, rotated=0))
    q0 = jnp.einsum("btd,dhk->bthk", h, lp["c_wq"])
    k0 = jnp.einsum("btd,dhk->bthk", h, lp["c_wk"])
    unit = lambda x: x / np.sqrt(np.mean(np.square(x), -1, keepdims=True)
                                 + 1e-5)
    for i in range(4):
        np.testing.assert_allclose(
            q[:, :, i], unit(0.5 * (q0[:, :, i] + k0[:, :, i // 2])),
            rtol=1e-4, atol=1e-5)
    for j in range(2):
        want = unit(0.5 * (0.5 * (q0[:, :, 2 * j] + q0[:, :, 2 * j + 1])
                           + k0[:, :, j])) * lp["c_beta"][j]
        np.testing.assert_allclose(k[:, :, j], want, rtol=1e-4, atol=1e-5)


def test_the_program_mixer_against_the_reference_mixer():
    h, lp = _mixer_inputs()
    got = shard_map(
        lambda h, lp: transformer._cca_mixer(CFG, h, lp), mesh=_mesh(),
        in_specs=(P(), P()), out_specs=P(), check_vma=False)(h, lp)
    with jax.default_matmul_precision("highest"):
        want = reference_zaya.attention(h, lp, MODEL)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_temperature_gradient_against_a_difference_quotient():
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 2, 16))
    beta = jnp.array([0.7, 1.3])
    f = lambda beta: jnp.sum(jnp.sin(
        transformer._rmsnorm(x, beta[:, None], 1e-5)))
    got = jax.grad(f)(beta)
    for j in range(2):
        step = jnp.zeros(2).at[j].set(1e-2)
        quotient = (f(beta + step) - f(beta - step)) / 2e-2
        np.testing.assert_allclose(got[j], quotient, rtol=2e-3)


# --- the router's state

def test_the_router_state_crosses_layers():
    """Layer 2's picks change when layer 1's W_down does. With layer 1's
    experts silenced (their W_d zero) the stream does not carry the
    change, so the state is its one way there: the picks still change,
    and with layer 2's gamma zero they do not. Program and reference pick
    alike throughout."""
    params, (tokens, labels) = _seeded(CFG), _batch()

    def picks(params):
        load = np.asarray(_loss_and_grad(CFG, _mesh(), params, tokens,
                                         labels)[1]["load"])
        np.testing.assert_array_equal(load, reference_zaya.step_readings(
            params, tokens, labels, MODEL)["load"])
        return load

    def moved(params):
        return dict(params,
                    r_down=params["r_down"].at[0, 0].multiply(-3.0))

    a, b = picks(params), picks(moved(params))
    assert (a[0] != b[0]).any() and (a[1] != b[1]).any()
    quiet = dict(params, wd=params["wd"].at[0, 0].set(0.0))
    a, b = picks(quiet), picks(moved(quiet))
    assert (a[0] != b[0]).any() and (a[1] != b[1]).any()
    cut = dict(quiet, r_gamma=quiet["r_gamma"].at[0, 1].set(0.0))
    a, b = picks(cut), picks(moved(cut))
    assert (a[0] != b[0]).any()
    np.testing.assert_array_equal(a[1], b[1])


# --- the share tied to the model

def _hidden(cfg, params, tokens):
    """The stack's output before the final norm, through the program."""
    mesh = _mesh()
    stage_fn = transformer._make_stage_fn(cfg, 1)
    return shard_map(
        lambda p, t: transformer._spmd_forward(cfg, stage_fn, p, t, 1,
                                               logits=False).hidden,
        mesh=mesh, in_specs=(transformer._param_specs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp"), check_vma=False)(
            shard_params(params, cfg, mesh), tokens)


def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer():
    one = dataclasses.replace(CFG, n_layers=1, layer_types=("cca",))
    whole = init_params(dataclasses.replace(one, n_experts_held=None),
                        jax.random.PRNGKey(3), 1)
    tokens, _ = _batch()
    halves = []
    for first in (0, 4):
        held = {k: (v[:, :, first:first + 4] if k in ("wg", "wu", "wd")
                    else v) for k, v in whole.items()}
        halves.append(_hidden(
            dataclasses.replace(one, first_expert_held=first), held, tokens))
    with jax.default_matmul_precision("highest"):
        lp = reference_zaya.layer_leaves(whole, 0)
        x = whole["embed"][tokens]
        uncut, _, load = reference_zaya.layer(
            x, jnp.zeros((B, T, 16)), lp, model=MODEL)
        a, b, c = lp["res1"]
        after_mixer = a * x + b + c * reference_zaya.attention(
            reference_zaya._rms(x, lp["ln1"], 1e-5), lp, MODEL)
    assert load[:4].sum() and load[4:].sum()  # both halves got tokens
    # Each half is the stream after the mixer plus its experts' part.
    np.testing.assert_allclose(halves[0] + halves[1] - after_mixer, uncut,
                               atol=3e-5)


def test_the_two_row_slices_log_sum_exps_combine_to_the_whole_tables():
    y = jax.random.normal(jax.random.PRNGKey(8), (B * T, 64))
    table = jax.random.normal(jax.random.PRNGKey(9), (V, 64))
    zero = jnp.zeros(B * T, jnp.int32)
    lse = []
    for rows in (table[:V // 2], table[V // 2:]):
        _, nll = block_nll(y, rows, zero, jnp.ones(B * T), 24, True, 1.0)
        lse.append(nll + y @ rows[0])  # nll = lse - the picked logit
    want = jax.nn.logsumexp(y @ table.T, axis=-1)
    np.testing.assert_allclose(jnp.logaddexp(*lse), want, rtol=1e-5)


# --- the head and the loss by blocks

def _head_inputs(tied=True):
    y = jax.random.normal(jax.random.PRNGKey(10), (B * T, 64))
    table = jax.random.normal(jax.random.PRNGKey(11),
                              (V, 64) if tied else (64, V)) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(12), (B * T,), 0, V)
    weights = jax.random.normal(jax.random.PRNGKey(13), (B * T,))
    return y, table, labels, weights


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("block", [16, 24, 80, 200])
def test_block_nll_equals_the_whole_head_in_loss_and_both_gradients(
        block, tied):
    """80 tokens: 16 divides them, 24 leaves a block of 8, 200 is more
    than there are."""
    y, table, labels, weights = _head_inputs(tied)

    def whole(y, table):
        logits = (y @ (table.T if tied else table)) * 0.5
        return jnp.sum(weights * token_nll(logits[None], labels[None])[0])

    def blocks(y, table):
        return block_nll(y, table, labels, weights, block, tied, 0.5)[0]

    want, want_grads = jax.value_and_grad(whole, (0, 1))(y, table)
    got, got_grads = jax.value_and_grad(blocks, (0, 1))(y, table)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-5 * np.abs(w).max() + 1e-6)


@pytest.mark.parametrize("tied", [True, False])
def test_a_cotangent_other_than_one_scales_both_gradients(tied):
    """The forward pass made the gradients for a cotangent of one: the
    backward pass has the loss's own to multiply them by."""
    y, table, labels, weights = _head_inputs(tied)

    def times(c):
        return jax.grad(lambda y, table: c * block_nll(
            y, table, labels, weights, 24, tied, 0.5)[0], (0, 1))(y, table)

    for one, three in zip(times(1.0), times(3.0)):
        assert np.abs(np.asarray(one)).max() > 1e-3
        np.testing.assert_allclose(three, 3.0 * one, rtol=1e-6)


def test_the_weights_gradient_is_each_tokens_cross_entropy():
    y, table, labels, weights = _head_inputs()
    nll = block_nll(y, table, labels, weights, 24, True, 0.5)[1]
    got = jax.grad(lambda w: 3.0 * block_nll(y, table, labels, w, 24, True,
                                             0.5)[0])(weights)
    np.testing.assert_allclose(got, 3.0 * nll, rtol=1e-6)


def test_the_per_token_output_takes_no_gradient():
    y, table, labels, weights = _head_inputs()
    grads = jax.grad(lambda y, table: jnp.sum(block_nll(
        y, table, labels, weights, 24, True, 0.5)[1]), (0, 1))(y, table)
    assert not any(np.asarray(g).any() for g in grads)


def _head_matmuls(jaxpr):
    """(einsum, whether under the transposed scope, operand and result
    types) of every ``dot_general`` under ``head_block`` in ``jaxpr``."""
    return [(path.rsplit("/", 1)[-1], "transpose(" in path,
             set(sum(_types(eqn), [])))
            for eqn, path in _eqns(jaxpr)
            if "head_block" in path and eqn.primitive.name == "dot_general"]


# 80 tokens by blocks of 24: the scan's body and a last block of 8.
BODIES = 2


def test_a_differentiated_step_forms_a_blocks_logits_once(bf16_step):
    """Three matmuls a block, all float32 and all in the forward pass: the
    logits, the hidden states' gradient, the table's. A fourth would be
    the logits formed again."""
    found = _head_matmuls(bf16_step)
    assert sorted(name for name, _, _ in found) == sorted(
        ["nd,vd->nv", "nv,vd->nd", "nv,nd->vd"] * BODIES)
    assert all(types == {jnp.dtype(jnp.float32)} for _, _, types in found)
    assert not any(transposed for _, transposed, _ in found)
    # What the backward pass has under ``head`` is the two products with
    # the cotangent and the final norm's own rule: no matmul.
    backward = [eqn.primitive.name for eqn, path in _eqns(bf16_step)
                if "transpose(jvp(forward))/head" in path]
    assert "mul" in backward and "dot_general" not in backward


@pytest.mark.parametrize("differentiated, matmuls, counted", [
    (False, 1, 0), (True, 3, 1)])
def test_gradients_are_made_where_the_loss_is_differentiated_and_counted(
        differentiated, matmuls, counted):
    """An evaluation (the benchmark's reference comparison) does a block's
    logits and nothing of the gradients; ``hvd.metrics()`` says which of
    the two a job traced."""
    params = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0),
                                                1))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    loss_fn = make_loss_fn(CFG, _mesh(), 1)
    fn = jax.grad(loss_fn) if differentiated else loss_fn
    metrics.reset()
    jaxpr = jax.make_jaxpr(fn)(params, tokens, tokens).jaxpr
    assert metrics.counters().get(
        "head.blocks_with_gradients_traced", 0) == counted
    assert len(_head_matmuls(jaxpr)) == matmuls * BODIES


def test_no_array_of_all_logits_is_in_the_lowered_step():
    params = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0),
                                                1))
    optimizer = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)

    def text(cfg):
        return make_train_step(cfg, optimizer, _mesh(),
                               n_microbatches=1).lower(
            params, jax.eval_shape(optimizer.init, trained(params)),
            tokens, tokens).as_text()

    # [b, t, V] and [N, V] of any type: the one-hot and a mask among them.
    whole = (f"tensor<{B}x{T}x{V}x", f"tensor<{B * T}x{V}x")
    assert not any(shape in text(CFG) for shape in whole)
    assert f"tensor<24x{V}xf32>" in text(CFG)
    assert whole[0] + "f32>" in text(dataclasses.replace(CFG,
                                                         head_block=None))


def test_the_whole_head_and_the_head_by_blocks_give_one_loss(both):
    cfg = dataclasses.replace(CFG, head_block=None)
    params, (tokens, labels) = _seeded(cfg), _batch()
    loss, _, grads = _loss_and_grad(cfg, _mesh(), params, tokens, labels)
    (want, _, want_grads), _, _ = both
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(grads["embed"], want_grads["embed"],
                               atol=1e-6)


# --- over tp, and what is refused

def test_tp_2_on_virtual_devices(both):
    params, (tokens, labels) = _seeded(CFG), _batch()
    loss, readings, grads = _loss_and_grad(CFG, _mesh(tp=2), params, tokens,
                                           labels)
    (want, want_readings, want_grads), _, _ = both
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_array_equal(readings["load"], want_readings["load"])
    for name in want_grads:
        np.testing.assert_allclose(
            grads[name], want_grads[name],
            atol=2e-5 * np.abs(np.asarray(want_grads[name])).max(),
            err_msg=name)


@pytest.mark.parametrize("axes, what", [
    (dict(sp=2), "sequence shards"), (dict(pp=3), "pipeline stages")])
def test_sp_and_pp_through_a_cca_layer_are_refused(axes, what):
    with pytest.raises(ValueError, match=what):
        make_loss_fn(CFG, _mesh(**axes), 1)


def test_packed_documents_through_a_cca_layer_are_refused():
    with pytest.raises(ValueError, match="packed documents through a cca"):
        make_loss_fn(CFG, _mesh(), 1, packed=True)


@pytest.mark.parametrize("change, what", [
    (dict(router_hidden=16, use_moe=False, expert_bias_rate=0.0),
     "router_hidden"),
    (dict(head_block=0), "head_block"),
    (dict(n_kv_heads=1, n_heads=4), "must be even"),
    (dict(partial_rotary_factor=0.3), "rotated channels"),
    (dict(qk_norm=True), "qk_norm"),
    (dict(remat_keeps=("cca_latent",)), "remat_keeps")])
def test_what_the_configuration_refuses(change, what):
    with pytest.raises(ValueError, match=what):
        dataclasses.replace(CFG, **change)


def test_the_bias_moves_by_the_rule_and_the_optimizer_never_sees_it():
    params, (tokens, labels) = _seeded(CFG), _batch()
    mesh = _mesh()
    optimizer = optax.adamw(3e-4)
    sharded = shard_params(params, CFG, mesh)
    state = optimizer.init(trained(sharded))
    assert "expert_bias" not in state[0].mu
    before = np.asarray(params["expert_bias"])
    new, _, loss, readings = make_train_step(
        CFG, optimizer, mesh, n_microbatches=1)(sharded, state, tokens,
                                                labels)
    want = reference_zaya.updated_bias(before[0], readings["load"], 1e-3)
    np.testing.assert_allclose(new["expert_bias"][0], want, atol=1e-7)
    assert np.isfinite(float(loss))
    assert readings["token_nll"].shape == (B, T)


# --- the other configurations' programs

# sha256 (first 16 hex digits) of ``make_train_step(...).lower(...).
# as_text()`` of a tiny preset of each decoder configuration the benchmark
# has, read on the commit before this block existed (PR 37's tree) in this
# installation (jax 0.9.0): the new mixer kind, router, residual scales and
# block-wise head are switches that leave every other program as it was. A
# PR that changes one of these programs on purpose reads the new value
# with this test's own ``_lowered`` and says so.
PRESETS = {
    "gpt2s": (dict(vocab=256, d_model=64, n_heads=4, d_head=16, d_ff=256,
                   n_layers=2, max_seq=64), "81e5340a03389cf9"),
    "olmoe": (dict(vocab=256, d_model=64, n_heads=4, d_head=16, n_layers=2,
                   max_seq=64, use_moe=True, n_experts=8, d_expert=32,
                   moe_top_k=2, router_aux_loss_coef=0.01,
                   router_z_loss_coef=0.001, norm="rmsnorm", qk_norm=True,
                   rope=True), "de9513a7de682d57"),
    "granite": (dict(vocab=256, d_model=64, n_heads=4, n_kv_heads=2,
                     d_head=16, d_ff=96, n_layers=3, max_seq=64,
                     layer_types=("mamba", "attention", "mamba"),
                     mamba_heads=4, mamba_d_head=16, mamba_d_state=8,
                     mamba_chunk=16, norm="rmsnorm", gated_mlp=True,
                     tie_embeddings=True, pos_table=False, remat=True,
                     embedding_multiplier=12.0, residual_multiplier=0.22,
                     logits_scaling=8.0, attention_multiplier=0.0078125),
                "473912acaab70c75"),
    "trinity": (dict(vocab=256, d_model=64, n_heads=4, n_kv_heads=2,
                     d_head=16, d_ff=96, n_layers=3, max_seq=64,
                     layer_types=("sliding_attention", "full_attention",
                                  "sliding_attention"), sliding_window=16,
                     qk_norm="head", attn_gate=True, post_norms=True,
                     pos_table=False, use_moe=True, num_dense_layers=1,
                     n_experts=16, n_experts_held=4, d_expert=32,
                     moe_top_k=3, moe_score_func="sigmoid",
                     norm_topk_prob=True, route_scale=2.5,
                     n_shared_experts=1, expert_bias_rate=1e-3,
                     norm="rmsnorm", gated_mlp=True, remat=True,
                     remat_keeps=("flash_out", "flash_lse")),
                "b5b595fe1a372e1b"),
    "glm": (dict(vocab=256, d_model=64, n_heads=4, d_head=16, d_ff=96,
                 n_layers=3, max_seq=64,
                 layer_types=("latent_attention",) * 3, q_lora_rank=24,
                 kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
                 rope_theta=1e6, pos_table=False, use_moe=True,
                 num_dense_layers=1, n_experts=16, n_experts_held=4,
                 d_expert=32, moe_top_k=3, moe_score_func="sigmoid",
                 norm_topk_prob=True, route_scale=1.8, n_shared_experts=1,
                 expert_bias_rate=1e-3, n_mtp_modules=1, norm="rmsnorm",
                 gated_mlp=True, remat=True,
                 remat_keeps=("flash_out", "flash_lse", "mla_cq")),
            "61f990a213310b23"),
}
# Read on PR 41's tree (c6c2cb2), before the carry had names and the layer
# kinds a table: this file's own tiny ZAYA configuration (every member of
# the carry and the bias step), and three programs no benchmark cell
# compiles, each a preset above with how its step is built: packed
# documents (segment ids ride the carry and are no output), two pipeline
# stages of two microbatches on virtual devices, and packed documents
# through expert layers (the ids ride between the activations and the
# router statistics).
PRESETS.update({
    "zaya": (dataclasses.asdict(CFG), "f7e2ee04389e00b7"),
    "gpt2s-packed": (PRESETS["gpt2s"][0], "1263c22b024831f7",
                     dict(packed=True)),
    "gpt2s-pp2": (PRESETS["gpt2s"][0], "ac3807fef6bc4af0",
                  dict(pp=2, n_microbatches=2)),
    "olmoe-packed": (PRESETS["olmoe"][0], "e623e3da09824462",
                     dict(packed=True)),
})


def _lowered(sizes, packed=False, pp=1, n_microbatches=1):
    cfg = TransformerConfig(**sizes)
    optimizer = optax.adamw(3e-4)
    params = jax.eval_shape(
        lambda k: init_params(cfg, k, pp), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return make_train_step(
        cfg, optimizer, _mesh(pp=pp), n_microbatches=n_microbatches,
        packed=packed).lower(
            params, jax.eval_shape(optimizer.init, trained(params)),
            *(tokens,) * (3 if packed else 2)).as_text()


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_the_other_configurations_lower_to_the_text_they_lowered_to(preset):
    sizes, want, *how = PRESETS[preset]
    got = hashlib.sha256(_lowered(sizes, **dict(*how)).encode()
                         ).hexdigest()[:16]
    assert got == want, (
        f"the {preset} preset's step lowers to another program than on the "
        f"commit this hash was read on ({got} != {want})")


# --- what the configuration states in float32 stays float32

def _not_float32(jaxpr):
    """By part, the equations of a traced bf16 train step that compute in
    another type than float32 (and how many were looked at): every norm's
    ``rsqrt`` (the q/k norm's under ``cca_norm`` counted apart, with the
    temperature's product); the router's four matmuls at the highest
    precision, its ``tanh`` (the gelus), softmax and top-1; the head's
    three matmuls by blocks; the loss's ``exp``, ``log`` and maximum; the
    bias's rule."""
    f32 = jnp.dtype(jnp.float32)
    parts = ("norms", "qk_norm", "router", "head", "loss", "bias")
    looked = {part: 0 for part in parts}
    wrong = {part: [] for part in parts}

    def hold(part, eqn, ok):
        looked[part] += 1
        if not ok:
            wrong[part].append(str(eqn))

    for eqn, path in _eqns(jaxpr):
        name = eqn.primitive.name
        ins, outs = _types(eqn)
        floats = [t for t in ins + outs if jnp.issubdtype(t, jnp.floating)]
        all_f32 = all(t == f32 for t in floats)
        if "zaya_router" in path and name == "dot_general":
            hold("router", eqn, all_f32 and "HIGHEST" in str(
                eqn.params["precision"]))
        elif ("zaya_router" in path and name == "tanh") or (
                "moe_route" in path and name in ("exp", "top_k")):
            hold("router", eqn, all_f32)
        elif "cca_norm" in path and name in ("rsqrt", "mul") and floats:
            hold("qk_norm", eqn, all_f32)
        elif name == "rsqrt":
            hold("norms", eqn, ins == [f32])
        elif "head_block" in path and name == "dot_general":
            hold("head", eqn, all_f32)
        elif "loss_block" in path and name in ("exp", "log", "reduce_max"):
            hold("loss", eqn, all_f32)
        elif "router_bias" in path and floats:
            hold("bias", eqn, all_f32)
    return wrong, looked


def _traced_bf16_step():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    mesh = _mesh()
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1), cfg,
                          mesh)
    assert params["c_wq"].dtype == params["c_conv1_k"].dtype == jnp.bfloat16
    for name in ("c_beta", "r_down", "r_gamma", "r_norm", "r_w1", "r_w3",
                 "res1", "res2", "expert_bias"):
        assert params[name].dtype == jnp.float32, name
    optimizer = optax.adamw(3e-4)
    tokens, labels = _batch()
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    return jax.make_jaxpr(step)(
        params, optimizer.init(trained(params)), tokens, labels).jaxpr


@pytest.fixture(scope="module")
def bf16_step():
    return _traced_bf16_step()


@pytest.fixture(scope="module")
def bf16_step_parts(bf16_step):
    return _not_float32(bf16_step)


@pytest.mark.parametrize("part, at_least", [
    ("norms", 2 * L + 1), ("qk_norm", 2 * L), ("router", 6 * L),
    ("head", 3 * BODIES), ("loss", 3 * BODIES), ("bias", 2)])
def test_a_bf16_step_computes_its_float32_parts_in_float32(
        bf16_step_parts, part, at_least):
    """What the cell's ``correct`` cannot tell apart on the chip for every
    part (the q/k norm, the head's logits or the block norms alone in
    bf16 read as the sound program does: PERF.md section 6, PR 38) is held
    here, in the traced step: the part's operations are there, and every
    one is float32."""
    wrong, looked = bf16_step_parts
    assert looked[part] >= at_least, looked
    assert not wrong[part], wrong[part]


def test_the_float32_check_sees_a_qk_norm_in_bf16(monkeypatch):
    rmsnorm = transformer._rmsnorm

    def norm(x, scale, eps):
        if x.ndim != 4:  # the block norms: as they are
            return rmsnorm(x, scale, eps)
        v = x.astype(jnp.bfloat16)
        return v * jax.lax.rsqrt(
            jnp.mean(jnp.square(v), -1, keepdims=True) + eps) * jnp.asarray(
                scale, jnp.bfloat16)

    monkeypatch.setattr(transformer, "_rmsnorm", norm)
    wrong, _ = _not_float32(_traced_bf16_step())
    assert wrong["qk_norm"]
    assert not any(v for k, v in wrong.items() if k != "qk_norm"), wrong


@pytest.mark.parametrize("seed", [0, 3])
def test_the_benchmark_starts_the_bias_where_the_rule_balances(seed):
    """``decoder_zaya.balanced_bias``: the rule applied to each layer's
    own scores at falling rates, layer by layer, leaves a bias under which
    the program's first step gives every expert of every layer its share
    of the tokens, where the seeded router with a zero bias does not."""
    from benchmark.runners import decoder_zaya

    params = init_params(CFG, jax.random.PRNGKey(seed), 1)
    tokens, labels = _batch(seed + 1)
    zero = _loss_and_grad(CFG, _mesh(), params, tokens, labels)[1]["load"]
    start = dict(first_rate=1e-2, last_rate=1e-6, applications=512)
    bias = decoder_zaya.balanced_bias(params, tokens, MODEL, start)
    assert bias.shape == params["expert_bias"].shape
    np.testing.assert_allclose(np.asarray(bias).sum(-1), 0, atol=1e-6)
    load = np.asarray(_loss_and_grad(
        CFG, _mesh(), {**params, "expert_bias": bias}, tokens,
        labels)[1]["load"])
    share = B * T // CFG.n_experts
    assert np.abs(load - share).max() <= 1, load
    assert np.abs(np.asarray(zero) - share).max() > 3, zero


def test_the_benchmarks_rate_rises_from_zero():
    from benchmark.runners import decoder_zaya

    optimizer = decoder_zaya.optimizer_of(dict(
        name="adamw", learning_rate=3e-4, warmup_steps=100))
    w = {"w": jnp.ones(3)}
    state = optimizer.init(w)
    moved = []
    for _ in range(3):
        updates, state = optimizer.update({"w": jnp.ones(3)}, state, w)
        moved.append(float(-updates["w"][0]))
    # Adam's first steps are the rate itself: 0, 3e-6, 6e-6 (and the decay).
    np.testing.assert_allclose(moved, [0.0, 3e-6 * 1.0001, 6e-6 * 1.0001],
                               rtol=1e-3, atol=1e-12)
    with pytest.raises(ValueError, match="optimizer"):
        decoder_zaya.optimizer_of(dict(name="sgd"))
