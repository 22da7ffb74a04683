"""The GLM-4.7-Flash (``glm4_moe_lite``) decoder as one chip of a deployment
holds it (latent attention with a shared rotated key head, a leading dense
layer, a sigmoid router with a balancing bias over experts of which a share
is held, a shared expert, one multi-token-prediction module in the loss)
against the benchmark's plain float32 reference
(``benchmark/reference_glm_lite.py``), at small widths on the CPU with
seeded weights."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference_glm_lite as reference
from horovod_tpu.common import metrics
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_train_step,
    shard_params)
from horovod_tpu.ops import pallas_attention
from horovod_tpu.training import init_opt_state
from test_afmoe import _eqns, _layer, _mesh, _types, _worst_leaf

LATENT = "latent_attention"
# 16 experts of which 4..7 are held, 3 a token; heads of 12 + 4 = 16.
CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, d_head=16, d_ff=48, n_layers=3,
    max_seq=64, layer_types=(LATENT,) * 3, q_lora_rank=12, kv_lora_rank=8,
    qk_rope_head_dim=4, qk_nope_head_dim=12, rope_theta=1e6, use_moe=True,
    num_dense_layers=1, n_experts=16, n_experts_held=4, first_expert_held=4,
    d_expert=16, moe_top_k=3, moe_score_func="sigmoid", route_scale=1.8,
    norm_topk_prob=True, n_shared_experts=1, expert_bias_rate=0.001,
    norm="rmsnorm", gated_mlp=True, pos_table=False, n_mtp_modules=1)
B, T = 2, 24
STATE = ("expert_bias", "mtp_expert_bias")


def _weights(cfg=CFG, seed=0):
    """Seeded weights with every norm's scale and both biases away from
    their defaults, so that one applied in the wrong place shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages=1)
    for at, name in enumerate(sorted(params)):
        key = jax.random.PRNGKey(seed + 100 + at)
        if name in STATE:
            params[name] = 0.1 * jax.random.normal(key, params[name].shape)
        elif "norm" in name or "ln" in name:
            params[name] = 1 + 0.1 * jax.random.normal(key,
                                                       params[name].shape)
    return params


def _batch(seed=1, shape=(B, T)):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 128)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _model(cfg=CFG):
    return dict(num_hidden_layers=cfg.n_layers,
                num_dense_layers=cfg.num_dense_layers,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                num_experts_per_tok=cfg.moe_top_k,
                route_scale=cfg.route_scale,
                mtp_loss_weight=cfg.mtp_loss_weight,
                load_balance_coeff=cfg.expert_bias_rate,
                first_expert_held=cfg.first_expert_held)


def _program(cfg, params, tokens, labels, **axes):
    """(loss, readings, gradient by every leaf)."""
    mesh = _mesh(**axes)
    data = NamedSharding(mesh, P("dp", "sp"))
    (loss, readings), grads = jax.jit(jax.value_and_grad(make_loss_fn(
        cfg, mesh, n_microbatches=1, with_readings=True), has_aux=True))(
        shard_params(params, cfg, mesh), jax.device_put(tokens, data),
        jax.device_put(labels, data))
    return float(loss), jax.device_get(readings), jax.device_get(grads)


@pytest.fixture(scope="module")
def stated():
    """The program and the reference on the same weights and batch."""
    params = _weights()
    tokens, labels = _batch()
    want_loss, want_grads = jax.jit(
        lambda p, t, l: reference.loss_and_grad(p, t, l, _model()))(
        params, tokens, labels)
    want = jax.device_get(reference.step_readings(params, tokens, labels,
                                                  _model()))
    return dict(params=params, tokens=tokens, labels=labels,
                got=_program(CFG, params, tokens, labels),
                want=dict(want, loss=float(want_loss),
                          grads=jax.device_get(want_grads)))


# ---- the whole model --------------------------------------------------------

def test_the_loss_and_both_cross_entropies_match_the_reference(stated):
    (loss, readings, _), want = stated["got"], stated["want"]
    assert abs(loss - want["loss"]) / want["loss"] < 1e-6
    np.testing.assert_allclose(readings["token_nll"], want["nll"],
                               atol=5e-6)
    np.testing.assert_allclose(readings["mtp_token_nll"], want["mtp_nll"],
                               atol=5e-6)
    # Loss = main + 0.3 x the module's mean over the positions with a
    # label two ahead; the last position has none and reads zero.
    module = readings["mtp_token_nll"]
    assert not module[:, -1].any() and module[:, :-1].all()
    assert loss == pytest.approx(
        readings["token_nll"].mean() + 0.3 * module.sum() / (B * (T - 1)),
        rel=1e-6)


def test_every_gradient_leaf_matches_the_reference(stated):
    (_, _, grads), want = stated["got"], stated["want"]["grads"]
    assert set(grads) == set(want) | set(STATE)
    worst = _worst_leaf(grads, want)
    assert max(worst.values()) < 2e-5, worst
    # The module's leaves are there, and the low-rank chains'.
    assert {"l_wqa", "l_wkvb", "mtp_eh", "mtp_l_wqa", "mtp_wg",
            "mtp_final_ln"} <= set(want)
    # A bias moves no loss: it picks, and the pick has no gradient.
    for name in STATE:
        assert not np.asarray(grads[name]).any()


def test_the_counts_are_every_expert_layers_and_the_modules_last(stated):
    load, want = stated["got"][1]["load"], stated["want"]["load"]
    assert load.shape == (CFG.n_layers + 1, CFG.n_experts)
    assert (load[0] == 0).all()  # the leading dense layer routes nothing
    np.testing.assert_array_equal(load[1:], want)
    assert (load[1:].sum(axis=1) == CFG.moe_top_k * B * T).all()
    assert stated["got"][1]["windows"].tolist() == [0, 1, 1, 1]


@pytest.mark.parametrize("axes", [dict(tp=2), dict(dp=2), dict(dp=2, tp=2)],
                         ids=["tp2", "dp2", "dp2-tp2"])
def test_sharded_layouts_give_what_one_device_gives(stated, axes):
    """The heads over ``tp`` (the down-projections, their norms and the
    shared rotated key whole on every member), the held experts over
    ``dp``, the module's too."""
    loss, readings, grads = _program(CFG, stated["params"],
                                     stated["tokens"], stated["labels"],
                                     **axes)
    one_loss, one, one_grads = stated["got"]
    assert abs(loss - one_loss) / one_loss < 1e-6
    np.testing.assert_array_equal(readings["load"], one["load"])
    np.testing.assert_allclose(readings["mtp_token_nll"],
                               one["mtp_token_nll"], atol=5e-6)
    worst = _worst_leaf(grads, {k: v for k, v in one_grads.items()
                                if k not in STATE})
    assert max(worst.values()) < 2e-5, worst


@pytest.mark.parametrize("keeps", [(), ("flash_out", "mla_cq", "mla_ckv"),
                                   ("attn_q", "attn_kv", "attn_proj")])
def test_a_rematerialized_layer_gives_the_same(stated, keeps):
    cfg = dataclasses.replace(CFG, remat=True, remat_keeps=keeps)
    loss, _, grads = _program(cfg, stated["params"], stated["tokens"],
                              stated["labels"])
    one_loss, _, one_grads = stated["got"]
    assert loss == pytest.approx(one_loss, rel=1e-6)
    worst = _worst_leaf(grads, {k: v for k, v in one_grads.items()
                                if k not in STATE})
    assert max(worst.values()) < 2e-5, worst


def test_bf16_stays_near_the_float32_reference():
    """bf16 parameters, activations and matmul operands with float32
    norms, router, heads and losses, on the same (bf16-rounded) weights,
    256 tokens: the loss within 1e-3, a token's cross-entropies by their
    rms within 0.1."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda a, like: a.astype(like.dtype), _weights(),
        jax.eval_shape(lambda k: init_params(cfg, k, 1),
                       jax.random.PRNGKey(0)))
    tokens, labels = _batch(shape=(4, 64))
    loss, readings, _ = _program(cfg, params, tokens, labels)
    want = reference.step_readings(params, tokens, labels, _model())
    assert abs(loss - float(want["loss"])) / float(want["loss"]) < 1e-3
    for got, ref in ((readings["token_nll"], want["nll"]),
                     (readings["mtp_token_nll"], want["mtp_nll"])):
        assert float(jnp.sqrt(jnp.mean(jnp.square(got - ref)))) < 0.1


# ---- the train step ---------------------------------------------------------

@pytest.mark.parametrize("remat", [False, True])
def test_the_train_step_moves_both_biases_by_its_own_counts(remat):
    cfg = dataclasses.replace(CFG, remat=remat)
    mesh = _mesh()
    params = shard_params(_weights(cfg), cfg, mesh)
    before = {k: np.asarray(params[k]) for k in STATE}
    optimizer = optax.adamw(1e-2)
    opt_state = init_opt_state(optimizer, transformer.trained(params), mesh)
    assert not set(STATE) & set(opt_state[0].mu)
    assert {"mtp_eh", "mtp_l_wkva", "l_wqb"} <= set(opt_state[0].mu)
    tokens, labels = _batch()
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    losses = []
    for _ in range(3):
        params, opt_state, loss, readings = step(params, opt_state, tokens,
                                                 labels)
        losses.append(float(loss))
        if len(losses) == 1:
            load = np.asarray(readings["load"])
            rows = {"expert_bias": load[1:CFG.n_layers],
                    "mtp_expert_bias": load[CFG.n_layers:]}
            for name in STATE:
                np.testing.assert_allclose(
                    np.asarray(params[name]), np.asarray(
                        reference.updated_bias(
                            before[name],
                            rows[name].reshape(before[name].shape), 0.001)),
                    atol=1e-7)
    assert losses[2] < losses[1] < losses[0]
    assert set(readings) == {"load", "windows", "token_nll",
                             "mtp_token_nll"}


def test_the_counters_and_the_spans_say_what_was_built():
    metrics.reset()
    mesh = _mesh()
    params = shard_params(init_params(CFG, jax.random.PRNGKey(0), 1), CFG,
                          mesh)
    make_train_step(CFG, optax.adamw(1e-3), mesh, n_microbatches=1)
    # Three latent layers in the stack and the module's.
    assert metrics.counters()["model.latent_layers"] == 4
    assert metrics.counters()["model.mtp_modules"] == 1
    spans = {s["name"]: s["counts"] for s in metrics.spans()}
    for name in ("state.shard", "step.build"):
        assert spans[name]["latent_layers"] == 4, name
        assert spans[name]["mtp_modules"] == 1, name
    assert spans["state.shard"]["leaves"] == len(params)
    # A model with neither says nothing of them.
    metrics.reset()
    plain = TransformerConfig(n_layers=1)
    shard_params(init_params(plain, jax.random.PRNGKey(0), 1), plain, mesh)
    make_train_step(plain, optax.adamw(1e-3), mesh, n_microbatches=1)
    assert not any(k.startswith("model.") for k in metrics.counters())
    assert all("latent_layers" not in s["counts"] for s in metrics.spans())
    metrics.reset()


# ---- the latent mixer -------------------------------------------------------

def _mixer_inputs():
    params = _weights()
    lp = {k: v[0, 0] for k, v in params.items() if k.startswith("l_")}
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, CFG.d_model))
    return h, lp


def _kernel_operands(h, lp):
    """q, k, v as the mixer hands them to the kernels."""
    seen = {}

    def attend(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, kw=kw)
        return jnp.zeros_like(q)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "context_parallel_attention", attend)
        transformer._latent_mixer(CFG, h, lp)
    return seen


def test_the_mixer_hands_the_kernels_the_references_q_k_v():
    h, lp = _mixer_inputs()
    seen = _kernel_operands(h, lp)
    with jax.default_matmul_precision("highest"):
        want = reference.queries_keys_values(h, lp, _model())
    for got, ref in zip((seen["q"], seen["k"], seen["v"]), want):
        assert got.shape == (B, T, CFG.n_heads, CFG.d_head)
        np.testing.assert_allclose(got, ref, atol=2e-5)
    assert seen["kw"]["causal"] is True and "window" not in seen["kw"]


def test_the_rotation_touches_the_last_channels_only():
    """Moving a token to another position changes the last
    ``qk_rope_head_dim`` channels of its query and key heads and nothing
    before them, and no channel of its value."""
    h, lp = _mixer_inputs()
    here = _kernel_operands(h, lp)
    there = _kernel_operands(jnp.roll(h, 5, axis=1), lp)  # token i at i + 5
    nope = CFG.qk_nope_head_dim
    for name in ("q", "k"):
        a, b = here[name], jnp.roll(there[name], -5, axis=1)
        np.testing.assert_allclose(a[..., :nope], b[..., :nope], atol=1e-6)
        assert float(jnp.abs(a[..., nope:] - b[..., nope:]).max()) > 0.1
    np.testing.assert_allclose(here["v"], jnp.roll(there["v"], -5, axis=1),
                               atol=1e-6)


def test_the_rotated_key_is_one_head_for_all():
    h, lp = _mixer_inputs()
    k = _kernel_operands(h, lp)["k"]
    nope = CFG.qk_nope_head_dim
    for head in range(1, CFG.n_heads):
        np.testing.assert_array_equal(k[:, :, head, nope:],
                                      k[:, :, 0, nope:])
        assert float(jnp.abs(k[:, :, head, :nope]
                             - k[:, :, 0, :nope]).max()) > 0.1
    # Position 0 is not rotated at all: the shared head is W_kva's last
    # columns applied to h.
    np.testing.assert_allclose(
        k[:, 0, 0, nope:], h[:, 0] @ lp["l_wkva"][:, CFG.kv_lora_rank:],
        atol=1e-5)


@pytest.mark.parametrize("last", [None, 16, 4])
def test_rope_rotates_the_last_channels_as_one_whole_head(last):
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 2, 16))
    pos = jnp.arange(6, dtype=jnp.int32)
    got = transformer._rope(x, pos, 1e4, last)
    n = 16 if last is None else last
    np.testing.assert_array_equal(got[..., :16 - n], x[..., :16 - n])
    np.testing.assert_allclose(
        got[..., 16 - n:], reference.rotate(x[..., 16 - n:], 1e4), atol=1e-6)
    # The whole-head call is the function every other model calls.
    if n == 16:
        np.testing.assert_array_equal(got, transformer._rotated(x, pos, 1e4))


def test_the_softmax_scale_is_of_the_whole_head():
    """Scores over sqrt(nope + rope): with the kernels' XLA twin, the
    mixer on two tokens is the reference's."""
    h, lp = _mixer_inputs()
    got = jax.jit(jax.shard_map(
        lambda h: transformer._latent_mixer(CFG, h, lp), mesh=_mesh(),
        in_specs=P(), out_specs=P(), check_vma=False))(h)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(h, lp, _model())
        narrow = reference.attention(
            h, dict(lp, l_wqb=lp["l_wqb"] * (16 / 12) ** 0.5), _model())
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(narrow - want).max()) > 1e-3


# ---- the held share ----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Eight members holding two of sixteen experts each: the routed
    parts all eight give, with the shared expert counted once, are what
    the uncut reference gives for the whole layer; every member counts
    the same tokens per expert, over all sixteen."""
    E, share, d, f = 16, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 7)
    x = jax.random.normal(ks[0], (2, 12, d))
    whole = {"router": jax.random.normal(ks[1], (d, E)) * d ** -0.5,
             "wg": jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
             "wu": jax.random.normal(ks[3], (E, d, f)) * d ** -0.5,
             "wd": jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
             "shared_wgu": jax.random.normal(ks[5], (d, 2, f)) * d ** -0.5,
             "shared_w2": jax.random.normal(ks[6], (f, d)) * f ** -0.5,
             "expert_bias": 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                    (E,))}
    model = dict(num_experts_per_tok=3, route_scale=1.8,
                 first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        routed, shared, want_load = reference.expert_layer(x, whole, model)
    total = np.zeros(x.shape, np.float32)
    for member in range(E // share):
        first = member * share
        mine = {k: (v[first:first + share] if k in ("wg", "wu", "wd") else v)
                for k, v in whole.items() if not k.startswith("shared")}
        y, load = _layer(x, mine, top_k=3, norm_topk_prob=True,
                         route_scale=1.8, first=first)
        np.testing.assert_array_equal(np.asarray(load),
                                      np.asarray(want_load))
        total += np.asarray(y)
    np.testing.assert_allclose(total, np.asarray(routed), atol=5e-6)
    assert float(np.abs(total).max()) > 0.1
    # The whole layer: the shares' sum and the shared expert once.
    np.testing.assert_allclose(total + np.asarray(shared),
                               np.asarray(routed + shared), atol=5e-6)
    assert float(np.abs(np.asarray(shared)).max()) > 0.1


# ---- the multi-token-prediction module --------------------------------------

def _module_call(monkeypatch, which, tokens, labels):
    """Argument ``which`` (3: hidden, 4: inputs, 5: targets) that
    ``_mtp_module`` is called with in the loss, read out as the module's
    "cross-entropy"."""
    def spy(*args):
        out = args[which].astype(jnp.float32)
        return out, jnp.zeros(()), None

    monkeypatch.setattr(transformer, "_mtp_module", spy)
    readings = _program(CFG, _weights(), tokens, labels)[1]
    return np.asarray(readings["mtp_token_nll"])


def test_the_module_embeds_the_next_token_and_predicts_the_one_after(
        monkeypatch):
    tokens, labels = _batch()
    inputs = _module_call(monkeypatch, 4, tokens, labels)
    targets = _module_call(monkeypatch, 5, tokens, labels)
    np.testing.assert_array_equal(inputs, labels)
    np.testing.assert_array_equal(inputs[:, :-1], tokens[:, 1:])
    np.testing.assert_array_equal(targets[:, :-2], tokens[:, 2:])


@pytest.mark.parametrize("wrong", ["embeds t_i", "predicts t_{i+1}",
                                   "hidden after the final norm",
                                   "halves swapped"])
def test_the_reference_sees_a_module_on_other_tokens(stated, wrong,
                                                     monkeypatch):
    """Each is another model: the loss moves, and only the module's
    part of it."""
    module = transformer._mtp_module
    params = dict(stated["params"])

    def other(cfg, layer_fn, p, hidden, inputs, targets):
        if wrong == "embeds t_i":
            inputs = jnp.roll(inputs, 1, axis=1)
        elif wrong == "predicts t_{i+1}":
            targets = inputs
        elif wrong == "hidden after the final norm":
            hidden = transformer._rmsnorm(hidden, p["final_ln"],
                                          cfg.norm_eps)
        return module(cfg, layer_fn, p, hidden, inputs, targets)

    if wrong == "halves swapped":
        params["mtp_eh"] = jnp.roll(params["mtp_eh"], CFG.d_model, axis=1)
    monkeypatch.setattr(transformer, "_mtp_module", other)
    loss, readings, _ = _program(CFG, params, stated["tokens"],
                                 stated["labels"])
    want = stated["want"]
    np.testing.assert_allclose(readings["token_nll"], want["nll"],
                               atol=5e-6)
    assert float(np.abs(readings["mtp_token_nll"]
                        - want["mtp_nll"]).max()) > 0.05
    assert abs(loss - want["loss"]) > 1e-4


def test_embed_and_head_sum_their_gradients_over_both_uses(stated):
    """``embed`` is read as the stack's input and as the module's;
    ``head`` by the main loss and by the module's. With the module's loss
    weight zero each keeps the main path's gradient alone; the difference
    is the module's, and the full gradient is their sum as the reference
    has it."""
    params, tokens, labels = (stated[k] for k in ("params", "tokens",
                                                  "labels"))
    _, _, full = stated["got"]
    _, _, main = _program(dataclasses.replace(CFG, mtp_loss_weight=0.0),
                          params, tokens, labels)
    _, _, twice = _program(dataclasses.replace(CFG, mtp_loss_weight=0.6),
                           params, tokens, labels)
    for name in ("embed", "head"):
        module = np.asarray(full[name]) - np.asarray(main[name])
        assert float(np.abs(module).max()) > 1e-4, name
        # Linear in the weight: main + 2 x the module's part.
        np.testing.assert_allclose(np.asarray(twice[name]),
                                   np.asarray(main[name]) + 2 * module,
                                   rtol=1e-4, atol=2e-6)
        np.testing.assert_allclose(full[name],
                                   stated["want"]["grads"][name],
                                   rtol=1e-4, atol=2e-6)
    # Without the module's loss its own leaves get nothing.
    assert not np.asarray(main["mtp_eh"]).any()
    assert np.asarray(full["mtp_eh"]).any()
    # A row of ``embed`` no token and no label names gets none.
    used = set(np.asarray(tokens).ravel()) | set(np.asarray(labels).ravel())
    unused = sorted(set(range(CFG.vocab)) - used)
    assert unused and not np.asarray(full["embed"])[unused].any()


def test_the_modules_layer_is_the_stacks_last_kind():
    """The module runs one more layer of the stack's last (mixer,
    feed-forward) pair, with leaves of that layer's names."""
    one = CFG.mtp_layer
    assert (one.kinds, one.ffn_kinds, one.n_mtp_modules) == (
        (LATENT,), ("moe",), 0)
    params = init_params(CFG, jax.random.PRNGKey(0), 1)
    layer = {k for k in transformer._param_specs(one)
             if k not in transformer._MODEL_LEAVES}
    assert {k[4:] for k in params if k.startswith("mtp_")} == layer | {
        "hnorm", "enorm", "eh", "final_ln"}
    assert params["mtp_eh"].shape == (1, 2 * CFG.d_model, CFG.d_model)
    assert params["mtp_wg"].shape == (1, 4, CFG.d_model, CFG.d_expert)
    # A dense stack's module ends in the dense MLP.
    dense = dataclasses.replace(CFG, use_moe=False, num_dense_layers=0,
                                expert_bias_rate=0.0)
    assert dense.mtp_layer.ffn_kinds == ("mlp",)
    names = set(init_params(dense, jax.random.PRNGKey(0), 1))
    assert "mtp_wgu" in names and "mtp_router" not in names
    mesh = _mesh()
    tokens, labels = _batch()
    loss = make_loss_fn(dense, mesh, n_microbatches=1)(
        shard_params(init_params(dense, jax.random.PRNGKey(0), 1), dense,
                     mesh), tokens, labels)
    assert np.isfinite(float(loss))


# ---- what the configuration states in float32 stays float32 ------------------

def _traced_bf16_step():
    cfg = dataclasses.replace(
        CFG, dtype=jnp.bfloat16, remat=True,
        remat_keeps=("flash_out", "mla_cq", "mla_ckv", "attn_q"))
    mesh = _mesh()
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1), cfg,
                          mesh)
    assert params["l_wqa"].dtype == params["mtp_eh"].dtype == jnp.bfloat16
    for name in ("l_qnorm", "l_kvnorm", "mtp_hnorm", "mtp_enorm",
                 "mtp_final_ln", "mtp_router", "mtp_expert_bias"):
        assert params[name].dtype == jnp.float32, name
    optimizer = optax.adamw(3e-4)
    opt_state = init_opt_state(optimizer, transformer.trained(params), mesh)
    tokens, labels = _batch()
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    return jax.make_jaxpr(step)(params, opt_state, tokens, labels).jaxpr


def _not_float32(jaxpr):
    """By part, the equations of a traced bf16 train step that compute in
    another type than float32 (and how many were looked at): every
    norm's ``rsqrt``, and the squares of the two low-rank norms (of an
    operand as wide as a rank, under ``mla_q`` or ``mla_kv``); the
    router's matmul at the highest precision,
    its scores and its top-k; the main head's matmul and the module's;
    the main loss's ``exp`` and ``log`` and the module's; the rule of
    both biases."""
    f32 = jnp.dtype(jnp.float32)
    parts = ("norms", "low_rank_norms", "router", "head", "mtp_head",
             "loss", "mtp_loss", "bias")
    looked = {part: 0 for part in parts}
    wrong = {part: [] for part in parts}
    ranks = (CFG.q_lora_rank, CFG.kv_lora_rank)

    def hold(part, eqn, ok):
        looked[part] += 1
        if not ok:
            wrong[part].append(str(eqn))

    for eqn, path in _eqns(jaxpr):
        name = eqn.primitive.name
        ins, outs = _types(eqn)
        floats = [t for t in ins + outs if jnp.issubdtype(t, jnp.floating)]
        module = "mtp_" if "/mtp" in path else ""
        if "moe_route" in path and name in ("dot_general", "logistic",
                                            "top_k"):
            highest = name != "dot_general" or "HIGHEST" in str(
                eqn.params["precision"])
            hold("router", eqn, highest and all(t == f32 for t in floats))
        elif name == "rsqrt":
            hold("norms", eqn, ins == [f32])
        elif "/head" in path and name == "dot_general":
            hold(module + "head", eqn, all(t == f32 for t in floats))
        elif "/loss" in path and name in ("exp", "log", "reduce_max"):
            hold(module + "loss", eqn, all(t == f32 for t in floats))
        elif "router_bias" in path and floats:
            hold("bias", eqn, all(t == f32 for t in floats))
        elif ("mla_q" in path or "mla_kv" in path) and name in (
                "square", "integer_pow") and eqn.invars[0].aval.shape[
                -1] in ranks:
            hold("low_rank_norms", eqn, ins == [f32])
    return wrong, looked


@pytest.fixture(scope="module")
def bf16_step_parts():
    return _not_float32(_traced_bf16_step())


@pytest.mark.parametrize("part, at_least", [
    # 2 block norms and 2 low-rank norms x 4 layers, the final norm, the
    # module's three
    ("norms", 4 * 4 + 4), ("low_rank_norms", 2 * 4), ("router", 3 * 3),
    ("head", 1), ("mtp_head", 1), ("loss", 3), ("mtp_loss", 3),
    ("bias", 2)])
def test_a_bf16_step_computes_its_float32_parts_in_float32(
        bf16_step_parts, part, at_least):
    """What the cell's ``correct`` cannot tell apart on the chip for
    every part is held here, in the traced step: the part's operations
    are there, and every one is float32."""
    wrong, looked = bf16_step_parts
    assert looked[part] >= at_least, looked
    assert not wrong[part], wrong[part]


def test_the_float32_check_sees_a_low_rank_norm_in_bf16(monkeypatch):
    bf16 = jnp.bfloat16
    rmsnorm = transformer._rmsnorm

    def norm(x, scale, eps):
        if x.shape[-1] not in (CFG.q_lora_rank, CFG.kv_lora_rank):
            return rmsnorm(x, scale, eps)
        v = x.astype(bf16)
        ms = jnp.mean(jnp.square(v), -1, keepdims=True)
        return (v * jax.lax.rsqrt(ms + eps)
                * scale.astype(bf16)).astype(x.dtype)

    monkeypatch.setattr(transformer, "_rmsnorm", norm)
    wrong, _ = _not_float32(_traced_bf16_step())
    assert wrong["low_rank_norms"] and wrong["norms"]
    assert not any(v for k, v in wrong.items()
                   if k not in ("low_rank_norms", "norms")), wrong


# ---- the kernels at the head width 256 --------------------------------------

@pytest.mark.parametrize("kind, chunk, grid, vmem_mb", [
    ("fwd", 4096, (40, 2, 2), 35.7), ("dq", 4096, (40, 2, 2), 39.8),
    ("dkv", 2048, (40, 4, 4), 27.3), ("bwd", 4096, (40, 1, 2, 2), 73.4)])
def test_the_plan_at_the_cells_shape(kind, chunk, grid, vmem_mb):
    """B 2 x H 20 merged, T 8,192, D 256, bf16, causal: one head a step
    at chunks of 4,096 forward and in the fused backward, which holds a
    head's dK and dV for all 8,192 rows besides (32 MiB of its own
    budget); of the two passes it replaces, chunks of 4,096 in the dQ
    pass and 2,048 in the dK/dV pass; the plans at D 64 and 128 are what
    they were."""
    plan = pallas_attention.kernel_plan(40, 8192, 8192, 256, jnp.bfloat16,
                                        True, kind=kind)
    assert (plan.heads, plan.chunk_q, plan.chunk_k, plan.grid) == (
        1, chunk, chunk, grid)
    assert (plan.tile_q, plan.tile_k, plan.tiles_visited) == (512, 512, 136)
    assert round(plan.vmem_bytes / 1e6, 1) == vmem_mb
    fused = kind == "bwd"
    assert plan.vmem_bytes <= (pallas_attention.BWD_VMEM_BUDGET if fused
                               else pallas_attention.VMEM_BUDGET)
    narrow = pallas_attention.kernel_plan(64, 8192, 8192, 128, jnp.bfloat16,
                                          True, kind=kind)
    assert (narrow.heads, narrow.chunk_q, narrow.grid) == (
        (1, 8192, (64, 1, 1, 1)) if fused else (1, 4096, (64, 2, 2)))


def test_the_kernels_at_a_head_of_two_lane_tiles_match_their_xla_twins(
        monkeypatch):
    """D 256 through the interpreted kernels, forward and backward,
    against the XLA path."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 256, 2, 256)) for key in ks)

    def loss(use_pallas):
        return lambda q, k, v: jnp.sum(jnp.square(
            pallas_attention.flash_attention(q, k, v, causal=True,
                                             use_pallas=use_pallas)))

    want, want_grads = jax.value_and_grad(loss(False), (0, 1, 2))(q, k, v)
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    got, grads = jax.value_and_grad(loss(True), (0, 1, 2))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-4)


# ---- what is not built raises -----------------------------------------------

@pytest.mark.parametrize("case, match", [
    ("value width", "a value width that differs from the key's is not "
                    "built"),
    ("odd rope", "an even qk_rope_head_dim"),
    ("no rank", "needs kv_lora_rank"),
    ("grouped", "n_kv_heads, qk_norm, attn_gate and attention_multiplier"),
    ("gate", "n_kv_heads, qk_norm, attn_gate and attention_multiplier"),
    ("two modules", "a chain of multi-token-prediction modules is not "
                    "built"),
    ("keeps", "remat_keeps names")])
def test_a_configuration_that_is_not_built_raises(case, match):
    changed = {"value width": dict(qk_nope_head_dim=8),
               "odd rope": dict(qk_rope_head_dim=3, qk_nope_head_dim=13),
               "no rank": dict(kv_lora_rank=0),
               "grouped": dict(n_kv_heads=2), "gate": dict(attn_gate=True),
               "two modules": dict(n_mtp_modules=2),
               "keeps": dict(remat=True, remat_keeps=("mla_c",))}[case]
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **changed)


@pytest.mark.parametrize("kind", ["latent", "module"])
@pytest.mark.parametrize("case, match", [
    ("sp", r"sequence shards \(sp > 1\) through a latent_attention layer "
           "or a multi-token-prediction module are not built"),
    ("pp", r"pipeline stages \(pp > 1\) through a latent_attention layer "
           "or a multi-token-prediction module are not built"),
    ("packed", "packed documents through a latent_attention layer or a "
               "multi-token-prediction module are not built")])
def test_a_layout_that_is_not_built_raises(kind, case, match):
    """Through the new kind without a module, and through a module after
    a stack of another kind."""
    cfg = dataclasses.replace(
        CFG, n_layers=2, layer_types=(LATENT,) * 2, num_dense_layers=0,
        expert_bias_rate=0.0, n_mtp_modules=0) if kind == "latent" else \
        TransformerConfig(n_layers=2, n_mtp_modules=1)
    axes = {} if case == "packed" else {case: 2}
    with pytest.raises(ValueError, match=match):
        make_loss_fn(cfg, _mesh(**axes), n_microbatches=1,
                     packed=case == "packed")


def test_the_dense_oracle_refuses_the_model():
    tokens, labels = _batch()
    with pytest.raises(ValueError, match="dense LayerNorm decoder only"):
        transformer.dense_reference_loss(CFG, _weights(), tokens, labels)


def test_heads_that_do_not_divide_tp_raise():
    cfg = dataclasses.replace(CFG, n_heads=5)
    with pytest.raises(ValueError, match="n_heads .5. must be divisible"):
        make_loss_fn(cfg, _mesh(tp=2), n_microbatches=1)


# ---- the other models' steps are what they were ------------------------------

S, F, M, A = "sliding_attention", "full_attention", "mamba", "attention"
OTHERS = {
    "dense": (TransformerConfig(
        vocab=128, d_model=32, n_heads=4, d_head=8, d_ff=64, n_layers=2,
        max_seq=32), "077f06e4730e6459", "69dc56d5b0f93406"),
    "moe": (TransformerConfig(
        vocab=128, d_model=32, n_heads=4, d_head=8, d_ff=64, n_layers=2,
        max_seq=32, use_moe=True, n_experts=4, d_expert=16, moe_top_k=2,
        norm="rmsnorm", qk_norm=True, rope=True, router_aux_loss_coef=0.01,
        router_z_loss_coef=0.001), "bf3eab9ef3a286d6", "d545ccfbef5904aa"),
    "hybrid": (TransformerConfig(
        vocab=128, d_model=32, n_heads=4, d_head=8, n_kv_heads=2, d_ff=64,
        n_layers=3, max_seq=32, layer_types=(M, M, A), mamba_heads=4,
        mamba_d_head=8, mamba_d_state=8, mamba_chunk=8, norm="rmsnorm",
        gated_mlp=True, tie_embeddings=True, pos_table=False,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attention_multiplier=0.0078125, remat=True),
        "fe47993067cb4771", "1438a3da25003f07"),
    "afmoe": (TransformerConfig(
        vocab=128, d_model=32, n_heads=4, d_head=8, n_kv_heads=2, d_ff=48,
        n_layers=5, max_seq=64, layer_types=(S, S, F, S, S),
        sliding_window=8, use_moe=True, num_dense_layers=1, n_experts=16,
        n_experts_held=4, first_expert_held=4, d_expert=16, moe_top_k=3,
        moe_score_func="sigmoid", route_scale=2.826, norm_topk_prob=True,
        n_shared_experts=1, expert_bias_rate=0.001, norm="rmsnorm",
        qk_norm="head", attn_gate=True, post_norms=True, gated_mlp=True,
        pos_table=False, embedding_multiplier=32 ** 0.5, remat=True,
        remat_keeps=("flash_out", "attn_q")),
        "1dd8917d5185132e", "f3a90d1fa6540a6e"),
}


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_models_traced_steps_are_unchanged(name):
    """The train step of a model of each family the benchmark has, traced
    at a small size, is the jaxpr the parent commit (196e2f3, PR 35)
    traced, equation for equation, and ``init_params`` draws the weights
    it drew: a model with neither a latent layer nor a module compiles to
    the program it compiled to. The digest is of the printed jaxpr with
    addresses taken out and each line's characters sorted (a set prints
    in the order of the process's hash seed). A PR that changes those
    steps on purpose writes its own digests here."""
    cfg, step_digest, weights_digest = OTHERS[name]
    params = init_params(cfg, jax.random.PRNGKey(0), 1)
    assert not [k for k in params if k.startswith(("l_", "mtp_"))]
    assert _digest("".join(
        f"{k}{v.shape}{float(jnp.sum(jnp.abs(v.astype(jnp.float32)))):.6f}"
        for k, v in sorted(params.items()))) == weights_digest
    mesh = _mesh()
    params = shard_params(params, cfg, mesh)
    optimizer = optax.adamw(3e-4)
    opt_state = init_opt_state(optimizer, transformer.trained(params), mesh)
    tokens = jnp.zeros((2, 16), jnp.int32)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(make_train_step(
        cfg, optimizer, mesh, n_microbatches=1))(params, opt_state, tokens,
                                                 tokens)))
    assert _digest("\n".join("".join(sorted(line))
                             for line in text.split("\n"))) == step_digest
