"""The Ling-3.0-flash (``bailing_hybrid``) decoder as one chip of a
deployment holds it (KDA layers around one latent layer with a value width
of its own, QK-norm and a gate a head; a leading dense layer; a sigmoid
router whose selection is limited to the best groups of experts, of which a
share is held; a shared expert; a multi-token-prediction module whose mixer
is stated) against the benchmark's plain float32 reference
(``benchmark/reference_ling.py``), at small widths on the CPU with seeded
weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference_ling as reference
from horovod_tpu.common import metrics
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_train_step,
    shard_params, trained)
from horovod_tpu.parallel import moe
from horovod_tpu.training import init_opt_state
from test_afmoe import (
    _eqns, _layer, _mesh, _sharded_layer, _types, _worst_leaf)

KDA, LATENT = "kda", "latent_attention"
KINDS = (KDA, KDA, LATENT, KDA)
# 32 experts in 8 groups of which 4 are kept, 4..11 held, 4 a token; KDA
# heads of 16; latent heads of 12 + 4 = 16 for queries and keys, 8 for
# values.
CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, d_head=16, d_ff=48, n_layers=4,
    max_seq=64, layer_types=KINDS, mtp_layer_type=LATENT, kda_head_dim=16,
    kda_chunk=16, q_lora_rank=0, kv_lora_rank=8, qk_rope_head_dim=4,
    qk_nope_head_dim=12, v_head_dim=8, qk_norm="head", attn_gate="head",
    rope_theta=6e6, use_moe=True, num_dense_layers=1, n_experts=32,
    n_experts_held=8, first_expert_held=4, d_expert=16, moe_top_k=4,
    moe_score_func="sigmoid", moe_n_group=8, moe_topk_group=4,
    route_scale=2.5, norm_topk_prob=True, n_shared_experts=1,
    expert_bias_rate=0.001, norm="rmsnorm", norm_eps=1e-6, gated_mlp=True,
    pos_table=False, n_mtp_modules=1)
B, T = 2, 40
STATE = ("expert_bias", "mtp_expert_bias")


def _weights(cfg=CFG, seed=0):
    """Seeded weights with every norm's scale, the decays' ``A`` and both
    biases away from their defaults, so that one applied in the wrong
    place shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages=1)
    for at, name in enumerate(sorted(params)):
        key = jax.random.PRNGKey(seed + 100 + at)
        noise = 0.1 * jax.random.normal(key, params[name].shape)
        if name in STATE or name.endswith("k_A"):
            params[name] = noise
        elif "norm" in name or "ln" in name or name.endswith(("l_gq",
                                                              "l_gk")):
            params[name] = 1 + noise
    return params


def _batch(seed=1, shape=(B, T)):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0, 128)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _model(cfg=CFG):
    return dict(layer_types=cfg.kinds, mtp_layer_type=cfg.mtp_kind,
                num_dense_layers=cfg.num_dense_layers,
                kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                kda_gate_floor=cfg.kda_gate_floor, n_group=cfg.moe_n_group,
                topk_group=cfg.moe_topk_group,
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
                               atol=1e-5)
    np.testing.assert_allclose(readings["mtp_token_nll"], want["mtp_nll"],
                               atol=1e-5)
    module = readings["mtp_token_nll"]
    assert not module[:, -1].any() and module[:, :-1].all()


def test_every_gradient_leaf_matches_the_reference(stated):
    (_, _, grads), want = stated["got"], stated["want"]["grads"]
    assert set(grads) == set(want) | set(STATE)
    worst = _worst_leaf(grads, want)
    assert max(worst.values()) < 5e-5, worst
    # Every KDA leaf, the latent layer's new ones, and the module's, whose
    # mixer is the latent one while the stack's last layer is KDA.
    assert {"k_wqkv", "k_conv", "k_wf", "k_fb", "k_A", "k_wbeta", "k_wg",
            "k_norm", "k_wo", "l_wq", "l_gq", "l_gk", "l_wgate",
            "mtp_l_wq", "mtp_l_wgate", "mtp_eh"} <= set(want)
    assert not any(name.startswith("mtp_k_") for name in want)
    for name in STATE:
        assert not np.asarray(grads[name]).any()


def test_the_counts_are_every_expert_layers_and_the_modules_last(stated):
    load, want = stated["got"][1]["load"], stated["want"]["load"]
    assert load.shape == (CFG.n_layers + 1, CFG.n_experts)
    assert (load[0] == 0).all()
    np.testing.assert_array_equal(load[1:], want)
    assert (load[1:].sum(axis=1) == CFG.moe_top_k * B * T).all()


@pytest.mark.parametrize("keeps", [(), ("kda_qkv", "kda_gates", "kda_out"),
                                   ("flash_out", "flash_lse", "attn_q")])
def test_a_rematerialized_layer_gives_the_same(stated, keeps):
    cfg = dataclasses.replace(CFG, remat=True, remat_keeps=keeps)
    loss, _, grads = _program(cfg, stated["params"], stated["tokens"],
                              stated["labels"])
    assert loss == pytest.approx(stated["got"][0], rel=1e-6)
    worst = _worst_leaf(grads, {k: v for k, v in stated["got"][2].items()
                                if k not in STATE})
    assert max(worst.values()) < 5e-5, worst


@pytest.mark.parametrize("axes", [dict(tp=2), dict(dp=2)])
def test_sharded_layouts_give_what_one_device_gives(stated, axes):
    """Heads over ``tp`` (the KDA mixer's, with its convolutions, decays
    and gates; the latent mixer's with its gate a head), sequences and the
    held experts over ``dp``."""
    cfg = CFG if "tp" in axes else dataclasses.replace(
        CFG, n_experts_held=None, first_expert_held=0)
    params = stated["params"] if "tp" in axes else _weights(cfg)
    loss, _, grads = _program(cfg, params, stated["tokens"],
                              stated["labels"], **axes)
    want_loss, _, want = stated["got"] if "tp" in axes else _program(
        cfg, params, stated["tokens"], stated["labels"])
    assert loss == pytest.approx(want_loss, rel=2e-6)
    worst = _worst_leaf(grads, {k: v for k, v in want.items()
                                if k not in STATE})
    assert max(worst.values()) < 5e-5, worst


def test_bf16_stays_near_the_float32_reference(stated):
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda v: v.astype(jnp.bfloat16) if v.ndim > 3 else v,
        stated["params"])
    loss, readings, _ = _program(cfg, params, stated["tokens"],
                                 stated["labels"])
    assert abs(loss - stated["want"]["loss"]) / stated["want"]["loss"] < 5e-3
    assert readings["token_nll"].dtype == np.float32


def test_the_train_step_moves_both_biases_and_counts_its_layers():
    mesh = _mesh()
    import optax

    optimizer = optax.adamw(3e-4)
    cfg = dataclasses.replace(CFG, remat=True, remat_keeps=())
    params = shard_params(_weights(cfg), cfg, mesh)
    state = init_opt_state(optimizer, trained(params), mesh)
    assert "expert_bias" not in state[0].mu and "k_wqkv" in state[0].mu
    before = metrics.counters()
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    after = metrics.counters()
    for name, n in (("model.kda_layers", 3), ("model.latent_layers", 2),
                    ("model.mtp_modules", 1),
                    ("model.router_groups_kept", 4)):
        assert after.get(name, 0) - before.get(name, 0) == n, name
    tokens, labels = _batch()
    biases = {k: np.asarray(params[k]) for k in STATE}
    losses = []
    for _ in range(3):
        params, state, loss, readings = step(params, state, tokens, labels)
        losses.append(float(loss))
    assert losses[2] < losses[0]
    for name in STATE:
        assert np.abs(np.asarray(params[name]) - biases[name]).max() > 0


# ---- the KDA mixer ----------------------------------------------------------

def _kda_leaves(seed=3, cfg=CFG):
    params = _weights(cfg, seed)
    return {k: v[0, 0] for k, v in params.items() if k.startswith("k_")}


def _in_mesh(fn, *args):
    """``fn`` under a one-device mesh with the four axes a mixer names."""
    from horovod_tpu.common.compat import shard_map

    return jax.jit(shard_map(fn, mesh=_mesh(), in_specs=P(), out_specs=P(),
                             check_vma=False))(*args)


def test_the_kda_mixer_is_the_references():
    lp = _kda_leaves()
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 32))
    got = _in_mesh(lambda h, lp: transformer._kda_mixer(CFG, h, lp), h, lp)
    with jax.default_matmul_precision("highest"):
        want = reference.kda(h, lp, _model())
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.05


def test_the_decay_is_bounded_by_its_floor_and_by_one():
    """``g = floor x sigmoid(exp(A) (a + b))`` lies in (floor, 0) whatever
    the projection gives, and the initialiser starts it slow."""
    a = jnp.array([-1e4, -3.0, 0.0, 3.0, 1e4]).reshape(1, 5, 1, 1)
    g = transformer._kda_decay(a, jnp.zeros((1, 1)), jnp.zeros((1,)), -5.0)
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    np.testing.assert_allclose(g[0, 2, 0, 0], -2.5)
    lp = {k: v[0, 0] for k, v in init_params(
        CFG, jax.random.PRNGKey(0), 1).items() if k.startswith("k_")}
    start = transformer._kda_decay(jnp.zeros((1, 1, 4, 16)), lp["k_fb"],
                                   lp["k_A"], -5.0)
    assert -0.11 < float(start.min()) and float(start.max()) < -0.9e-3


def test_a_kda_layer_reads_no_later_token():
    lp = _kda_leaves()
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 32))
    mixer = lambda h, lp: transformer._kda_mixer(CFG, h, lp)
    first = _in_mesh(mixer, h, lp)
    second = _in_mesh(mixer, h.at[:, 25:].add(1.0), lp)
    np.testing.assert_allclose(first[:, :25], second[:, :25], atol=1e-6)
    assert float(jnp.abs(first[:, 25:] - second[:, 25:]).max()) > 1e-3


# ---- the latent mixer's new parts -------------------------------------------

def test_the_latent_mixer_hands_the_kernels_the_references_q_k_v(
        monkeypatch):
    """No query rank, QK-norm over a head's channels before the rotation,
    values of their own width: q, k as the reference makes them, v its
    own with zeros up to the keys' width."""
    cfg = CFG
    lp = {k: v[0, 0] for k, v in _weights().items() if k.startswith("l_")}
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 32))
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v)
        return v

    monkeypatch.setattr(transformer, "context_parallel_attention", spy)
    transformer._latent_mixer(cfg, h, lp)  # no axis is named without it
    with jax.default_matmul_precision("highest"):
        q, k, v = reference.queries_keys_values(h, lp, _model())
    assert seen["q"].shape == seen["k"].shape == seen["v"].shape == (
        2, 24, 4, 16)
    np.testing.assert_allclose(seen["q"], q, atol=2e-6)
    np.testing.assert_allclose(seen["k"], k, atol=2e-6)
    np.testing.assert_allclose(seen["v"][..., :8], v, atol=2e-6)
    assert not np.asarray(seen["v"][..., 8:]).any()


def test_the_latent_mixer_with_its_gate_is_the_references():
    lp = {k: v[0, 0] for k, v in _weights().items() if k.startswith("l_")}
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 32))
    got = _in_mesh(lambda h, lp: transformer._latent_mixer(CFG, h, lp), h,
                   lp)
    with jax.default_matmul_precision("highest"):
        want = reference.latent_attention(h, lp, _model())
    np.testing.assert_allclose(got, want, atol=2e-6)
    # One gate a head: [d, H], not a gate a channel.
    assert lp["l_wgate"].shape == (32, 4)
    ungated = dict(lp, l_wgate=jnp.zeros_like(lp["l_wgate"]))
    with jax.default_matmul_precision("highest"):
        half = reference.latent_attention(h, ungated, _model())
    assert float(jnp.abs(half - want).max()) > 1e-3


def test_the_modules_mixer_is_stated_and_not_the_stacks_last():
    assert CFG.kinds[-1] == KDA and CFG.mtp_kind == LATENT
    assert CFG.mtp_layer.kinds == (LATENT,)
    assert CFG.mixer_kinds == KINDS + (LATENT,)
    unstated = dataclasses.replace(CFG, mtp_layer_type=None)
    assert unstated.mtp_kind == KDA
    shapes = jax.eval_shape(lambda k: init_params(unstated, k, 1),
                            jax.random.PRNGKey(0))
    assert "mtp_k_wqkv" in shapes and "mtp_l_wq" not in shapes


# ---- the router's groups ----------------------------------------------------

def _moe_params(E=32, d=16, f=8, seed=9):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {"router": jax.random.normal(ks[1], (d, E)) * d ** -0.5,
            "wg": jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
            "wu": jax.random.normal(ks[3], (E, d, f)) * d ** -0.5,
            "wd": jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
            "shared_wgu": jax.random.normal(ks[5], (d, 2, f)) * d ** -0.5,
            "shared_w2": jax.random.normal(ks[6], (f, d)) * f ** -0.5,
            "expert_bias": 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                   (E,))}


MODEL = dict(num_experts_per_tok=4, route_scale=2.5, first_expert_held=0,
             n_group=8, topk_group=4)


@pytest.mark.parametrize("n_group, topk_group", [(8, 4), (4, 1), (2, 2),
                                                 (8, 8)])
def test_the_selection_is_the_definitions(n_group, topk_group):
    """The picks of ``moe._in_best_groups`` + top-k are the reference's,
    which sorts where the program takes top-k; every pick lies in a kept
    group, and a kept group is one of the best by its two largest."""
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2),
                                              (3, 50, 32)))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    model = dict(MODEL, n_group=n_group, topk_group=topk_group)
    want = np.asarray(reference.selection(scores, bias, model))
    limited = moe._in_best_groups(scores + bias, n_group, topk_group)
    picks = np.asarray(jax.lax.top_k(limited, 4)[1])
    got = np.zeros(want.shape, bool)
    np.put_along_axis(got, picks, True, axis=-1)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == 4).all()
    size = 32 // n_group
    groups = np.asarray(scores + bias).reshape(3, 50, n_group, size)
    two = np.sort(groups, -1)[..., -2:].sum(-1)
    kept = np.argsort(-two, -1)[..., :topk_group]
    assert all(e // size in kept[b, t] for b in range(3) for t in range(50)
               for e in picks[b, t])
    if topk_group < n_group:
        plain = np.asarray(jax.lax.top_k(scores + bias, 4)[1])
        assert (np.sort(plain, -1) != np.sort(picks, -1)).any()


def test_one_group_is_the_layer_as_it_was():
    """``n_group`` 1 is no instruction: the traced layer is the one that
    names no group, and all groups kept select as one group does."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 16))
    params = {k: v for k, v in _moe_params().items()
              if not k.startswith("shared")}
    kw = dict(top_k=4, norm_topk_prob=True, route_scale=2.5, first=0)
    plain = jax.make_jaxpr(_sharded_layer(params, **kw))(x, params)
    one = jax.make_jaxpr(_sharded_layer(
        params, n_group=1, topk_group=1, **kw))(x, params)
    assert str(plain) == str(one)
    y, load = _layer(x, params, top_k=4, norm_topk_prob=True,
                     route_scale=2.5, first=0)
    all_kept, same = _layer(x, params, top_k=4, norm_topk_prob=True,
                            route_scale=2.5, first=0, n_group=8,
                            topk_group=8)
    np.testing.assert_allclose(all_kept, y, atol=1e-6)
    np.testing.assert_array_equal(same, load)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four members holding eight of 32 experts each under the group-
    limited router: the routed parts all four give, with the shared expert
    counted once, are what the uncut reference gives for the whole layer;
    every member counts the same tokens per expert, over all 32."""
    E, share = 32, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    whole = _moe_params()
    with jax.default_matmul_precision("highest"):
        routed, shared, want_load = reference.expert_layer(x, whole, MODEL)
    total = np.zeros(x.shape, np.float32)
    for member in range(E // share):
        first = member * share
        mine = {k: (v[first:first + share] if k in ("wg", "wu", "wd") else v)
                for k, v in whole.items() if not k.startswith("shared")}
        y, load = _layer(x, mine, top_k=4, norm_topk_prob=True,
                         route_scale=2.5, first=first, n_group=8,
                         topk_group=4)
        np.testing.assert_array_equal(np.asarray(load),
                                      np.asarray(want_load))
        total += np.asarray(y)
    np.testing.assert_allclose(total, np.asarray(routed), atol=5e-6)
    assert float(np.abs(total).max()) > 0.1
    np.testing.assert_allclose(total + np.asarray(shared),
                               np.asarray(routed + shared), atol=5e-6)
    assert float(np.abs(np.asarray(shared)).max()) > 0.1


# ---- what the configuration states in float32 stays float32 -----------------

def _not_float32(jaxpr):
    """The equations under the KDA mixer's ``kda_gate`` and ``kda_scan``
    scopes of a traced bf16 step that form a decay, a gate, a norm or a
    sum of decays in another type than float32: (how many were looked at,
    those that were not)."""
    f32 = jnp.dtype(jnp.float32)
    looked, wrong = 0, []
    for eqn, path in _eqns(jaxpr):
        if "kda_gate" not in path and "kda_scan" not in path:
            continue
        if eqn.primitive.name not in ("exp", "logistic", "rsqrt", "cumsum"):
            continue
        ins, outs = _types(eqn)
        looked += 1
        if not all(t == f32 for t in ins + outs
                   if jnp.issubdtype(t, jnp.floating)):
            wrong.append(str(eqn))
    return looked, wrong


def _bf16_step(cfg=CFG):
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    mesh = _mesh()
    params = jax.eval_shape(lambda k: init_params(cfg, k, 1),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    return jax.make_jaxpr(make_loss_fn(cfg, mesh, n_microbatches=1))(
        params, tokens, tokens)


def test_a_bf16_step_forms_its_decays_gates_and_norms_in_float32():
    looked, wrong = _not_float32(_bf16_step())
    assert looked >= 3 * 8 and not wrong, wrong[:3]


def test_the_float32_check_sees_a_decay_in_bf16(monkeypatch):
    def rounded(a, bias, A, floor):
        x = (jnp.exp(A)[:, None] * (a.astype(jnp.float32) + bias))
        return floor * jax.nn.sigmoid(x.astype(jnp.bfloat16)).astype(
            jnp.float32)

    monkeypatch.setattr(transformer, "_kda_decay", rounded)
    assert _not_float32(_bf16_step())[1]


# ---- what is not built ------------------------------------------------------

@pytest.mark.parametrize("case, match", [
    ("no head", "needs kda_head_dim"),
    ("floor", r"kda_gate_floor \(-8.0\) must lie in \[-5, 0\)"),
    ("wide value", "wider than a key"),
    ("gate", "one gate a head is built through latent_attention layers "
             "alone"),
    ("groups", "moe_topk_group"),
    ("softmax groups", "groups of a sigmoid router"),
    ("module kind", "mtp_layer_type names the kind"),
    ("keeps", "remat_keeps names")])
def test_a_configuration_that_is_not_built_raises(case, match):
    changed = {
        "no head": dict(kda_head_dim=0), "floor": dict(kda_gate_floor=-8.0),
        "wide value": dict(v_head_dim=24),
        "gate": dict(layer_types=(KDA, KDA, "attention", KDA)),
        "groups": dict(moe_topk_group=9),
        "softmax groups": dict(moe_score_func="softmax",
                               expert_bias_rate=0.0),
        "module kind": dict(mtp_layer_type="ssm"),
        "keeps": dict(remat=True, remat_keeps=("kda",))}[case]
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **changed)


@pytest.mark.parametrize("case, match", [
    ("packed", "packed documents through a kda layer are not built"),
    ("sp", r"sequence shards \(sp > 1\) through a kda layer are not built"),
    ("pp", r"pipeline stages \(pp > 1\) through a kda layer are not built")])
def test_a_layout_that_is_not_built_raises(case, match):
    cfg = TransformerConfig(n_layers=2, layer_types=(KDA, KDA),
                            kda_head_dim=16, norm="rmsnorm")
    axes = {} if case == "packed" else {case: 2}
    with pytest.raises(ValueError, match=match):
        make_loss_fn(cfg, _mesh(**axes), n_microbatches=1,
                     packed=case == "packed")
