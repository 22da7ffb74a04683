"""The Trinity (``afmoe``) decoder as one chip of a deployment holds it
(sliding-window and full attention layers in one stack, gated attention,
per-head QK-norm, four norms a layer, a sigmoid router with a balancing
bias over experts of which a share is held, a shared expert, a leading
dense layer, an embedding multiplier) against the benchmark's plain
float32 reference (``benchmark/reference_afmoe.py``), at small widths on
the CPU with seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference_afmoe
from horovod_tpu.models import transformer
from horovod_tpu.parallel import moe
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_train_step,
    shard_params)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state
from test_parallel import _run_moe_layer

SLIDING, FULL = "sliding_attention", "full_attention"
PATTERN = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
# Every mechanism on: 16 experts of which 4..7 are held, 3 a token.
CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, d_head=8, n_kv_heads=2, d_ff=48,
    n_layers=5, max_seq=64, layer_types=PATTERN, sliding_window=8,
    use_moe=True, num_dense_layers=1, n_experts=16, n_experts_held=4,
    first_expert_held=4, d_expert=16, moe_top_k=3,
    moe_score_func="sigmoid", route_scale=2.826, norm_topk_prob=True,
    n_shared_experts=1, expert_bias_rate=0.001, norm="rmsnorm",
    qk_norm="head", attn_gate=True, post_norms=True, gated_mlp=True,
    pos_table=False, embedding_multiplier=32 ** 0.5)
# Every mechanism off: full attention without positions, all experts
# held, the scores as they are.
BARE = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, d_head=8, n_kv_heads=2, d_ff=48,
    n_layers=2, max_seq=64, layer_types=(FULL, FULL), use_moe=True,
    n_experts=8, d_expert=16, moe_top_k=3, moe_score_func="sigmoid",
    norm="rmsnorm", gated_mlp=True, pos_table=False)
B, T = 2, 24  # three windows of 8


def _weights(cfg, seed=0, n_stages=1):
    """Seeded weights with the norms' scales and the bias away from their
    defaults, so that one applied in the wrong place shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages=n_stages)
    names = [n for n in ("ln1", "ln2", "ln1_post", "ln2_post", "final_ln",
                         "gq", "gk") if n in params]
    for key, name in zip(jax.random.split(jax.random.PRNGKey(seed + 1),
                                          len(names)), names):
        params[name] = 1 + 0.1 * jax.random.normal(key, params[name].shape)
    if "expert_bias" in params:
        params["expert_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 2), params["expert_bias"].shape)
    return params


def _batch(seed=1, vocab=128, shape=(B, T)):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _mesh(**axes):
    axes = dict(dict(dp=1, pp=1, sp=1, tp=1), **axes)
    return build_parallel_mesh(jax.devices()[:int(np.prod(list(
        axes.values())))], **axes)


def _program(cfg, params, tokens, labels, **axes):
    """(loss, tokens per expert by layer, gradient by every leaf)."""
    mesh = _mesh(**axes)
    data = NamedSharding(mesh, P("dp", "sp"))
    (loss, readings), grads = jax.jit(jax.value_and_grad(make_loss_fn(
        cfg, mesh, n_microbatches=1, with_readings=True), has_aux=True))(
        shard_params(params, cfg, mesh), jax.device_put(tokens, data),
        jax.device_put(labels, data))
    return float(loss), np.asarray(readings["load"]), jax.device_get(grads)


def _model(cfg):
    return dict(layer_types=cfg.kinds, num_dense_layers=cfg.num_dense_layers,
                sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.norm_eps,
                num_experts_per_tok=cfg.moe_top_k,
                route_norm=cfg.norm_topk_prob, route_scale=cfg.route_scale,
                embedding_multiplier=cfg.embedding_multiplier,
                load_balance_coeff=cfg.expert_bias_rate,
                first_expert_held=cfg.first_expert_held)


def _reference(cfg, params, tokens, labels, **changed):
    model = dict(_model(cfg), **changed)
    loss, grads = jax.jit(lambda p, t, l: reference_afmoe.loss_and_grad(
        p, t, l, model))(params, tokens, labels)
    return float(loss), jax.device_get(grads)


def _worst_leaf(got, want):
    """Largest difference over the reference's largest entry, by leaf."""
    return {k: float(np.abs(np.asarray(got[k], np.float32)
                            - np.asarray(want[k], np.float32)).max()
                     / np.abs(np.asarray(want[k], np.float32)).max())
            for k in want}


# ---- the whole model --------------------------------------------------------

def test_loss_and_every_gradient_leaf_match_the_reference_in_float32():
    params = _weights(CFG)
    tokens, labels = _batch()
    loss, load, grads = _program(CFG, params, tokens, labels)
    want_loss, want = _reference(CFG, params, tokens, labels)
    assert abs(loss - want_loss) / want_loss < 1e-6
    assert set(grads) == set(want) | {"expert_bias"}
    worst = _worst_leaf(grads, want)
    assert max(worst.values()) < 2e-5, worst
    # The bias moves no loss: it picks, and the pick has no gradient.
    assert not np.asarray(grads["expert_bias"]).any()
    readings = reference_afmoe.step_readings(params, tokens, labels,
                                             _model(CFG))
    assert (load[0] == 0).all()  # the leading dense layer routes nothing
    np.testing.assert_array_equal(load[1:], np.asarray(readings["load"]))
    assert (load[1:].sum(axis=1) == CFG.moe_top_k * B * T).all()


@pytest.mark.parametrize("top_k, band_loss, band_grad, flips", [
    (16, 5e-4, 0.1, 0), (3, 2e-3, 0.3, 40)], ids=["all-picked", "top-3"])
def test_bf16_loss_and_gradients_stay_near_the_float32_reference(
        top_k, band_loss, band_grad, flips):
    """bf16 parameters, activations and matmul operands with float32
    norms, router, scores, top-k, bias, head and loss, on the same
    (bf16-rounded) weights, 256 tokens. With all 16 experts picked no
    pick can differ from the reference's and what is left is rounding:
    the loss read 2.0e-4 off, the gradient leaves 0.5 to 6.9 % in relative
    L2. With the model's 3 of 16 the router, which reads bf16
    activations, picks otherwise in 23 of 3,072 assignments; each moves
    its token's output by a whole expert, and a gradient's L2 goes with
    the root of the share of tokens moved: loss 8.1e-4, leaves 2.3 to
    21.4 %. What a float32 part in bf16 does is held on the chip, where
    it shows (benchmark/runners/decoder_afmoe.py)."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, moe_top_k=top_k)
    params = jax.tree_util.tree_map(
        lambda a, like: a.astype(like.dtype), _weights(cfg),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), 1)))
    assert params["wg"].dtype == params["wgate"].dtype == jnp.bfloat16
    assert params["router"].dtype == params["expert_bias"].dtype \
        == params["ln1_post"].dtype == jnp.float32
    tokens, labels = _batch(shape=(4, 64))
    loss, load, grads = _program(cfg, params, tokens, labels)
    ref_loss, ref_grads = _reference(cfg, params, tokens, labels)
    assert abs(loss - ref_loss) / ref_loss < band_loss
    far = {k: float(np.linalg.norm(np.asarray(grads[k], np.float32)
                                   - ref_grads[k].astype(np.float32))
                    / np.linalg.norm(ref_grads[k].astype(np.float32)))
           for k in ref_grads}
    assert max(far.values()) < band_grad, far
    assert all(grads[k].dtype == params[k].dtype for k in params)
    moved = np.abs(load[1:] - np.asarray(reference_afmoe.step_readings(
        params, tokens, labels, _model(cfg))["load"])).sum() // 2
    assert moved <= flips


@pytest.mark.parametrize("remat", [False, True])
def test_the_train_step_moves_the_bias_by_its_own_counts(remat):
    """The bias is no trained parameter: no moments, no weight decay, no
    gradient; the step moves it by the rule from the tokens each expert
    got in that step, and AdamW moves everything else."""
    cfg = dataclasses.replace(CFG, remat=remat, remat_keeps=())
    mesh = _mesh()
    params = shard_params(_weights(cfg), cfg, mesh)
    before = jax.device_get(params)
    optimizer = optax.adamw(1e-2, weight_decay=0.5)
    opt_state = init_opt_state(optimizer, transformer.trained(params), mesh)
    moments = opt_state[0].mu
    assert set(moments) == set(params) - {"expert_bias"}
    tokens, labels = _batch()
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    assert step.__name__ == "hvd_decoder_bias_step"
    params, opt_state, loss, readings = step(params, opt_state, tokens,
                                             labels)
    want_loss, want_load, _ = _program(cfg, before, tokens, labels)
    assert abs(float(loss) - want_loss) / want_loss < 1e-6
    np.testing.assert_array_equal(np.asarray(readings["load"]), want_load)
    # Every token's cross-entropy of the step's own forward pass: the
    # loss is its mean, and the reference's is the same token by token.
    nll = np.asarray(readings["token_nll"])
    assert nll.shape == tokens.shape
    assert abs(float(nll.mean()) - float(loss)) / float(loss) < 1e-6
    # By hand: rate * sign(mean - count), centred, the expert layers'.
    counts = want_load[1:].astype(np.float32)
    delta = 0.001 * np.sign(counts.mean(-1, keepdims=True) - counts)
    by_hand = before["expert_bias"][0] + delta - delta.mean(-1,
                                                            keepdims=True)
    np.testing.assert_allclose(np.asarray(params["expert_bias"])[0],
                               by_hand, rtol=0, atol=1e-7)
    readings = reference_afmoe.step_readings(before, tokens, labels,
                                             _model(cfg))
    np.testing.assert_allclose(np.asarray(params["expert_bias"]),
                               np.asarray(readings["bias"]), rtol=0,
                               atol=1e-7)
    # Weight decay 0.5 at rate 1e-2 would have shrunk it by 0.5 %.
    assert float(np.abs(np.asarray(params["expert_bias"])
                        - before["expert_bias"]).max()) <= 0.002
    assert not np.allclose(np.asarray(params["wg"]), before["wg"])


# ---- each mechanism alone ---------------------------------------------------

MECHANISMS = {
    "window": dict(layer_types=(SLIDING, SLIDING), sliding_window=8),
    "gate": dict(attn_gate=True),
    "qk_norm_per_head": dict(qk_norm="head"),
    "post_norms": dict(post_norms=True),
    "route_norm": dict(norm_topk_prob=True),
    "route_scale": dict(route_scale=2.826),
    "bias": dict(expert_bias_rate=0.001),
    "shared_expert": dict(n_shared_experts=1),
    "leading_dense_layer": dict(num_dense_layers=1),
    "embedding_multiplier": dict(embedding_multiplier=32 ** 0.5),
    "held_share": dict(n_experts_held=3, first_expert_held=2),
}


@pytest.fixture(scope="module")
def bare_loss():
    tokens, labels = _batch()
    return _program(BARE, _weights(BARE), tokens, labels)[0]


def test_the_bare_model_matches_the_reference():
    params = _weights(BARE)
    tokens, labels = _batch()
    loss, _, grads = _program(BARE, params, tokens, labels)
    want_loss, want = _reference(BARE, params, tokens, labels)
    assert abs(loss - want_loss) / want_loss < 1e-6
    assert max(_worst_leaf(grads, want).values()) < 2e-5


@pytest.mark.parametrize("name", sorted(MECHANISMS))
def test_each_mechanism_alone_matches_the_reference(name, bare_loss):
    """One mechanism on, the others off: the program and the reference
    agree, and both differ from the bare model (the mechanism is in
    it)."""
    cfg = dataclasses.replace(BARE, **MECHANISMS[name])
    params = _weights(cfg)
    tokens, labels = _batch()
    loss, _, grads = _program(cfg, params, tokens, labels)
    want_loss, want = _reference(cfg, params, tokens, labels)
    assert abs(loss - want_loss) / want_loss < 1e-6
    worst = _worst_leaf(grads, want)
    assert max(worst.values()) < 2e-5, worst
    assert abs(loss - bare_loss) / bare_loss > 1e-5


@pytest.mark.parametrize("off_by", [-1, 1])
def test_the_window_is_i_minus_j_below_w_on_both_sides(off_by):
    """T 24 holds rows on both sides of ``i - j = W``: a reference whose
    window is one wider or narrower is another model."""
    cfg = dataclasses.replace(BARE, **MECHANISMS["window"])
    params = _weights(cfg)
    tokens, labels = _batch()
    loss = _program(cfg, params, tokens, labels)[0]
    right = _reference(cfg, params, tokens, labels)[0]
    wrong = _reference(cfg, params, tokens, labels,
                       sliding_window=cfg.sliding_window + off_by)[0]
    assert abs(loss - right) < 1e-6 * right
    assert abs(loss - wrong) > 1e-4 * right


@pytest.mark.parametrize("wrong", [(SLIDING, SLIDING), (FULL, FULL),
                                   (FULL, SLIDING)])
def test_rope_and_window_are_the_sliding_layers_alone(wrong):
    """A sliding layer rotates and has the window, a full layer has
    neither: the reference told another pattern is another model."""
    cfg = dataclasses.replace(BARE, layer_types=(SLIDING, FULL),
                              sliding_window=T + 1)  # no key is cut off
    params = _weights(cfg)
    tokens, labels = _batch()
    loss = _program(cfg, params, tokens, labels)[0]
    right = _reference(cfg, params, tokens, labels)[0]
    other = _reference(cfg, params, tokens, labels, layer_types=wrong)[0]
    assert abs(loss - right) < 1e-6 * right
    assert abs(loss - other) > 1e-5 * right


def test_the_three_attention_kinds_share_their_leaves():
    params = init_params(CFG, jax.random.PRNGKey(0), 1)
    assert set(params) == set(transformer._param_specs(CFG))
    assert params["wq"].shape[:2] == params["wgate"].shape[:2] == (1, 5)
    assert params["gq"].shape == params["gk"].shape == (1, 5, 8)
    assert params["ln1_post"].shape == params["ln2_post"].shape == (1, 5, 32)
    assert params["wgu"].shape[:2] == (1, 1)      # the leading dense layer
    assert params["router"].shape == (1, 4, 32, 16)  # scores all 16
    assert params["wg"].shape == (1, 4, 4, 32, 16)   # holds 4 of them
    assert params["shared_wgu"].shape == (1, 4, 32, 2, 16)
    assert params["expert_bias"].shape == (1, 4, 16)
    assert params["expert_bias"].dtype == jnp.float32
    assert not np.asarray(params["expert_bias"]).any()
    assert set(transformer.trained(params)) == set(params) - {"expert_bias"}


# ---- the router -------------------------------------------------------------

def _moe_params(E=8, held=None, d=16, f=8, seed=0):
    held = E if held is None else held
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
            "wg": jax.random.normal(ks[1], (held, d, f)) * d ** -0.5,
            "wu": jax.random.normal(ks[2], (held, d, f)) * d ** -0.5,
            "wd": jax.random.normal(ks[3], (held, f, d)) * f ** -0.5}


def _sharded_layer(params, reads=("load",), **kw):
    """The program's expert layer on one device as a function of (x,
    params), which may hold an ``expert_bias``; it returns the result and
    the ``reads`` of its statistics."""
    def run(x, p):
        y, stats = transformer.moe_layer(
            x, p, p["router"].shape[-1], axis_name="dp",
            score_func="sigmoid", **kw)
        return (y,) + tuple(stats[k] for k in reads)

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
    return jax.shard_map(
        run, mesh=mesh, in_specs=(P(), {k: P() for k in params}),
        out_specs=(P(),) * (1 + len(reads)), check_vma=False)


def _layer(x, params, **kw):
    return jax.jit(_sharded_layer(params, **kw))(x, params)


def test_the_bias_picks_and_does_not_weigh():
    """A bias that lifts expert 5 over every score sends every token
    there; the token's other two picks and all three weights are the
    scores' own, so the result is the reference's, and a bias that is the
    same for all experts changes nothing."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 16))
    params = _moe_params()
    model = dict(num_experts_per_tok=3, route_norm=True, route_scale=2.0,
                 first_expert_held=0)
    plain, plain_load = _layer(x, params, top_k=3, norm_topk_prob=True,
                               route_scale=2.0, first=0)
    lifted = dict(params, expert_bias=jnp.zeros(8).at[5].set(2.0))
    y, load = _layer(x, lifted, top_k=3, norm_topk_prob=True,
                     route_scale=2.0, first=0)
    assert int(load[5]) == 24 and int(plain_load[5]) < 24
    assert int(load.sum()) == int(plain_load.sum()) == 3 * 24
    routed, _, want_load = reference_afmoe.expert_layer(x, lifted, model)
    np.testing.assert_allclose(np.asarray(y), np.asarray(routed), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert float(jnp.abs(y - plain).max()) > 1e-3
    shifted = dict(params, expert_bias=jnp.full(8, 0.7))
    same, same_load = _layer(x, shifted, top_k=3, norm_topk_prob=True,
                             route_scale=2.0, first=0)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(same_load),
                                  np.asarray(plain_load))


def test_the_weights_are_normalised_over_all_picked_held_or_not():
    """With 2 of 8 experts held, a token's weights still divide by the
    sum over its three picks: normalised over the held ones alone they
    would be larger wherever a pick fell elsewhere."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 16))
    whole = _moe_params()
    held = {k: (v if k == "router" else v[2:4]) for k, v in whole.items()}
    y, load = _layer(x, held, top_k=3, norm_topk_prob=True, first=2)
    model = dict(num_experts_per_tok=3, route_norm=True, route_scale=1.0,
                 first_expert_held=2)
    routed, _, want_load = reference_afmoe.expert_layer(x, held, model)
    np.testing.assert_allclose(np.asarray(y), np.asarray(routed), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert int(load.sum()) == 3 * 24 and 0 < int(load[2:4].sum()) < 3 * 24
    # Over the held alone: scores of the held picks over their own sum.
    s = jax.nn.sigmoid(x @ whole["router"])
    picked = jnp.any(jax.lax.top_k(s, 3)[1][..., None] == jnp.arange(8), -2)
    w = jnp.where(picked, s, 0.0)[..., 2:4]
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    wrong = sum(w[..., e, None] * (
        (jax.nn.silu(x @ held["wg"][e]) * (x @ held["wu"][e]))
        @ held["wd"][e]) for e in range(2))
    assert float(jnp.abs(wrong - y).max()) > 1e-2


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight members holding two of sixteen experts each: the routed
    parts all eight give, with the shared expert counted once, are what
    the uncut reference gives for the whole layer; every member counts
    the same tokens per expert, over all sixteen."""
    E, share, d, f = 16, 2, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, d))
    whole = _moe_params(E=E, d=d, f=f)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    whole["shared_wgu"] = jax.random.normal(ks[0], (d, 2, f)) * d ** -0.5
    whole["shared_w2"] = jax.random.normal(ks[1], (f, d)) * f ** -0.5
    whole["expert_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                   (E,))
    model = dict(num_experts_per_tok=3, route_norm=True, route_scale=2.826,
                 first_expert_held=0)
    routed, shared, want_load = reference_afmoe.expert_layer(x, whole, model)
    total = np.zeros(x.shape, np.float32)
    for member in range(E // share):
        first = member * share
        mine = {k: (v[first:first + share] if k in ("wg", "wu", "wd") else v)
                for k, v in whole.items() if not k.startswith("shared")}
        y, load = _layer(x, mine, top_k=3, norm_topk_prob=True,
                         route_scale=2.826, first=first)
        np.testing.assert_array_equal(np.asarray(load),
                                      np.asarray(want_load))
        part = reference_afmoe.expert_layer(
            x, mine, dict(model, first_expert_held=first))[0]
        np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                   atol=2e-6)
        total += np.asarray(y)
    np.testing.assert_allclose(total, np.asarray(routed), atol=5e-6)
    assert float(np.abs(total).max()) > 0.1
    # The whole layer: the shares' sum and the shared expert once.
    uncut = np.asarray(routed + shared)
    np.testing.assert_allclose(total + np.asarray(shared), uncut, atol=5e-6)
    assert float(np.abs(np.asarray(shared)).max()) > 0.1


def test_a_softmax_router_over_members_still_takes_its_share_from_the_mesh():
    """``first`` None: member m of ep holds E / ep experts from m E / ep,
    and a count that does not add up is refused."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 16))
    params = _moe_params(E=8)
    one, _ = _run_moe_layer(x, params, 1, top_k=2)
    two, _ = _run_moe_layer(x, params, 2, top_k=2)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), atol=2e-6)
    short = {k: (v if k == "router" else v[:6]) for k, v in params.items()}
    with pytest.raises(ValueError, match="say which are held"):
        _run_moe_layer(x, short, 1, top_k=2)
    with pytest.raises(ValueError, match="score_func"):
        _run_moe_layer(x, params, 1, top_k=2, score_func="tanh")


# ---- windows of the sorted assignments --------------------------------------

# 32 experts of which 8..11 are held, 4 a token, 1,024 tokens: 4,096
# assignments, and a window of twice the held share at balance is 1,024.
W_E, W_FIRST, W_HELD, W_K, W_D, W_TOKENS, W_ROWS = 32, 8, 4, 4, 16, 1024, 1024
W_MODEL = dict(num_experts_per_tok=W_K, route_norm=True, route_scale=2.0,
               first_expert_held=W_FIRST)


def _held_share(seed=0):
    whole = _moe_params(E=W_E, d=W_D, seed=seed)
    return {k: (v if k == "router" else v[W_FIRST:W_FIRST + W_HELD])
            for k, v in whole.items()}


def _kinds_of_token(all_held, one_held):
    """Tokens and a router that leave no pick to chance: the first
    ``all_held`` tokens pick the four held experts, the next ``one_held``
    one held expert and three others, the rest four others. The first
    three coordinates say which; the others are noise that no pick reads."""
    kind = np.full(W_TOKENS, 2)
    kind[:all_held], kind[all_held:all_held + one_held] = 0, 1
    x = np.array(jax.random.normal(jax.random.PRNGKey(5),
                                   (2, W_TOKENS // 2, W_D)))
    x[..., :3] = 10.0 * np.eye(3)[np.random.RandomState(0).permutation(
        kind)].reshape(2, -1, 3)
    params = _held_share()
    router = np.array(params["router"])
    router[:3] = 0.0
    router[0, W_FIRST:W_FIRST + W_HELD] = 1.0
    router[1, [W_FIRST + 1, 0, 1, 2]] = 1.0
    router[2, 3:7] = 1.0
    return jnp.asarray(x), dict(params, router=jnp.asarray(router))


def _bias_on_held(size):
    return jnp.zeros(W_E).at[W_FIRST:W_FIRST + W_HELD].set(size)


def _windows_case(case):
    """(x, params, held rows, windows) of a case of the test below."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, W_TOKENS // 2, W_D))
    if case == "fits":
        return x, _held_share(), None, 1
    if case == "every-pick-held":
        return (x, dict(_held_share(), expert_bias=_bias_on_held(5.0)),
                W_K * W_TOKENS, W_K * W_TOKENS // W_ROWS)
    if case == "no-pick-held":
        return x, dict(_held_share(), expert_bias=_bias_on_held(-5.0)), 0, 1
    all_held, one_held, windows = {
        "ends-on-the-edge": (W_ROWS // W_K, 0, 1),
        "one-row-past-the-edge": (W_ROWS // W_K, 1, 2),
        "one-row-short-of-the-edge": (W_ROWS // W_K - 1, 3, 1),
        "two-windows-to-the-edge": (2 * W_ROWS // W_K, 0, 2)}[case]
    return (*_kinds_of_token(all_held, one_held),
            W_K * all_held + one_held, windows)


@pytest.mark.parametrize("case", [
    "fits", "every-pick-held", "no-pick-held", "ends-on-the-edge",
    "one-row-past-the-edge", "one-row-short-of-the-edge",
    "two-windows-to-the-edge"])
def test_windows_of_the_sort_give_the_reference_whatever_the_routing(case):
    """The held experts' part is computed a window of the sorted
    assignments at a time, as many windows as the held rows need: values,
    counts and the gradients of the tokens, the router and the three held
    matrices are the reference's when the rows fit one window, when every
    pick is held (all ``top_k N / C`` windows: nothing has a capacity),
    when none is (zero, and zero gradients for the matrices), and when
    the rows end on a window's edge or one row to either side of it."""
    x, params, held_rows, windows = _windows_case(case)
    assert moe._window_rows(W_K * W_TOKENS, W_HELD, W_E) == W_ROWS
    layer = _sharded_layer(params, reads=("load", "windows"), top_k=W_K,
                           norm_topk_prob=True, route_scale=2.0,
                           first=W_FIRST)

    def program(x, params):
        y, load, taken = layer(x, params)
        return jnp.sum(jnp.sin(y)), (y, load, taken)

    def reference(x, params):
        y, _, load = reference_afmoe.expert_layer(x, params, W_MODEL)
        return jnp.sum(jnp.sin(y)), (y, load)

    (_, (y, load, taken)), grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(x, params)
    (_, (want_y, want_load)), want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(x, params)
    assert int(taken) == windows
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert int(load.sum()) == W_K * W_TOKENS
    if held_rows is not None:
        assert int(load[W_FIRST:W_FIRST + W_HELD].sum()) == held_rows
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=5e-6)
    for got, ref in [(grads[0], want[0])] + [
            (grads[1][k], want[1][k]) for k in ("router", "wg", "wu", "wd")]:
        got, ref = np.asarray(got), np.asarray(ref)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, ref, atol=5e-6 * max(1.0, float(np.abs(ref).max())))
    if held_rows == 0:
        assert not np.asarray(y).any()
        assert not any(np.asarray(grads[1][k]).any()
                       for k in ("wg", "wu", "wd"))
    else:
        assert float(np.abs(np.asarray(want_y)).max()) > 0.1


@pytest.mark.parametrize("count", [0, 1, 700, 1024])
def test_the_chips_sums_over_a_tokens_rows_are_the_lookups(count,
                                                           monkeypatch):
    """A window's sums over a token's rows as the chip computes them (the
    rows gathered token-major, a grouped matmul over blocks of 256 tokens;
    here in the Pallas interpreter) against the masked lookup of every
    pick that runs elsewhere: a window of 1,024 positions of 4,096
    sorted assignments of which the first ``count`` count, the rest
    holding what must not be read."""
    rows, fan, d, start = 1024, 4, 128, 1024
    order = jax.random.permutation(jax.random.PRNGKey(0), fan * W_TOKENS)
    index = order[start:start + rows]
    place = jnp.argsort(order) - start
    table = jax.random.normal(jax.random.PRNGKey(1), (rows, d))
    table = jnp.where(jnp.arange(rows)[:, None] < count, table, jnp.nan)
    want = moe._placed_sums(table, place, index, count, fan, jnp.float32)
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    got = moe._placed_sums(table, place, index, count, fan, jnp.float32)
    assert got.shape == (W_TOKENS, d) and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    by_hand = np.zeros((W_TOKENS, d), np.float32)
    np.add.at(by_hand, np.asarray(index[:count]) // fan,
              np.asarray(table[:count]))
    np.testing.assert_allclose(np.asarray(want), by_hand, atol=1e-6)


def _control_flow(jaxpr):
    return sorted({eqn.primitive.name for eqn, _ in _eqns(jaxpr)}
                  & {"while", "cond", "scan"})


@pytest.mark.parametrize("ep, held", [(1, W_E), (2, W_E // 2), (1, W_HELD)],
                         ids=["all-held", "ep-2", "an-eighth-held"])
def test_the_plain_layer_is_the_windows_body_called_once(ep, held):
    """All experts held, or half of them as a member of two: a window of
    twice the held share holds every assignment, and the layer is its
    body once, with no loop and no conditional around it, forward or
    backward. An eighth held: the loop over windows, in both."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:ep]), ("dp",))
    params = _moe_params(E=W_E, held=held * ep, d=W_D)
    x = jnp.zeros((2, W_TOKENS // 2, W_D))

    def loss(x, p):
        return jnp.sum(transformer.moe_layer(
            x, p, W_E, None if held * ep == W_E else W_FIRST, top_k=W_K,
            axis_name="dp")[0])

    specs = {k: P() if k == "router" or ep == 1 else P("dp") for k in params}
    traced = jax.make_jaxpr(jax.grad(jax.shard_map(
        loss, mesh=mesh, in_specs=(P("dp"), specs), out_specs=P(),
        check_vma=False), argnums=(0, 1)))(x, params)
    assert _control_flow(traced.jaxpr) == (
        ["while"] if held == W_HELD else [])


def test_the_members_windows_add_up_to_the_uncut_layer():
    """Four members holding two of eight experts each, 1,024 tokens, two
    a token: a member's window is half the 2,048 gathered assignments, so
    each runs the loop over windows between the all-gather and the
    reduce-scatter, and together they give what one device that holds
    all eight gives, and the dense oracle."""
    from test_parallel import _dense_moe_oracle

    E, d, top_k = 8, 16, 2
    params = _moe_params(E=E, d=d)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256, d))
    assert moe._window_rows(top_k * 1024, E // 4, E) == 1024
    uncut, _ = _run_moe_layer(x, params, 1, top_k=top_k)
    shared, stats = _run_moe_layer(x, params, 4, top_k=top_k)
    assert 1 <= int(stats["windows"]) <= 2
    np.testing.assert_allclose(np.asarray(shared), np.asarray(uncut),
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(shared).reshape(-1, d),
        _dense_moe_oracle(np.asarray(x).reshape(-1, d), params, top_k),
        rtol=1e-3, atol=1e-4)
    assert int(np.asarray(stats["load"]).sum()) == top_k * 1024


def test_the_reader_of_the_windows_takes_the_largest_of_the_last_step():
    from types import SimpleNamespace as Ns

    from benchmark.layer_metrics import moe_share_windows

    assert moe_share_windows.read(Ns(job=Ns())) is None
    assert moe_share_windows.read(Ns(job=Ns(readings=None))) is None
    assert moe_share_windows.read(Ns(job=Ns(readings={"load": 1}))) is None
    assert moe_share_windows.read(Ns(job=Ns(readings={
        "windows": jnp.asarray([0, 1, 3, 1, 1], jnp.int32)}))) == 3


# ---- the pattern ------------------------------------------------------------

def test_the_stage_walks_runs_of_one_mixer_and_feed_forward_pair():
    """Published layers 1-5 with one leading dense layer: four runs, each
    reading its groups' stacks from its own row."""
    assert CFG.ffn_kinds == ("mlp",) + ("moe",) * 4
    assert transformer._runs(CFG.stage_pattern(1)) == [
        (SLIDING, "mlp", {None: 0, "attention": 0, "mlp": 0}, 1),
        (SLIDING, "moe", {None: 1, "attention": 1, "moe": 0}, 1),
        (FULL, "moe", {None: 2, "attention": 2, "moe": 1}, 1),
        (SLIDING, "moe", {None: 3, "attention": 3, "moe": 2}, 2)]
    two_dense = dataclasses.replace(CFG, num_dense_layers=2)
    assert [run[:2] + run[3:] for run in transformer._runs(
        two_dense.stage_pattern(1))] == [
        (SLIDING, "mlp", 2), (FULL, "moe", 1), (SLIDING, "moe", 2)]


def test_a_period_repeated_over_two_stages_gives_what_one_stage_gives():
    cfg = dataclasses.replace(
        CFG, layer_types=(SLIDING, FULL) * 2, n_layers=4,
        num_dense_layers=0)
    staged = _weights(cfg, n_stages=2)
    flat = {k: v if v.ndim < 2 or k in ("embed", "head")
            else v.reshape((1, -1) + v.shape[2:]) for k, v in staged.items()}
    tokens, labels = _batch()
    want_loss, want_load, want = _program(cfg, flat, tokens, labels)
    loss, load, grads = _program(cfg, staged, tokens, labels, pp=2)
    assert abs(loss - want_loss) / want_loss < 1e-6
    np.testing.assert_array_equal(load, want_load)
    grads = {k: v.reshape(want[k].shape) for k, v in grads.items()}
    assert not grads.pop("expert_bias").any()
    want = {k: want[k] for k in grads}
    assert max(_worst_leaf(grads, want).values()) < 1e-5


@pytest.mark.parametrize("axes", [dict(tp=2), dict(dp=2), dict(dp=2, tp=2)],
                         ids=["tp2", "dp2", "dp2-tp2"])
def test_sharded_layouts_give_what_one_device_gives(axes):
    """tp over the heads, the gate, the dense and the shared widths; dp
    over the batch and over the held experts (each member of dp holds
    half of them)."""
    params = _weights(CFG)
    tokens, labels = _batch()
    want_loss, want_load, want = _program(CFG, params, tokens, labels)
    loss, load, grads = _program(CFG, params, tokens, labels, **axes)
    assert abs(loss - want_loss) / want_loss < 1e-6
    np.testing.assert_array_equal(load, want_load)
    worst = _worst_leaf(grads, {k: want[k] for k in want
                                if k != "expert_bias"})
    assert max(worst.values()) < 1e-5, worst


@pytest.mark.parametrize("case", ["pp", "sp", "packed", "packed_step",
                                  "held", "dense", "bias", "keeps",
                                  "window"])
def test_what_is_not_built_raises(case):
    if case == "pp":
        even = dataclasses.replace(CFG, n_layers=4, layer_types=PATTERN[:4])
        with pytest.raises(ValueError, match="leading dense layer"):
            make_loss_fn(even, _mesh(pp=2), n_microbatches=1)
    elif case == "sp":
        with pytest.raises(ValueError, match="balancing bias is not built"):
            make_loss_fn(CFG, _mesh(sp=2), n_microbatches=1)
    elif case == "packed":
        with pytest.raises(ValueError, match="packed documents"):
            make_loss_fn(CFG, _mesh(), n_microbatches=1, packed=True)
    elif case == "packed_step":
        cfg = dataclasses.replace(BARE, layer_types=None,
                                  expert_bias_rate=0.001)
        with pytest.raises(ValueError, match="packed documents"):
            make_train_step(cfg, optax.adamw(1e-3), _mesh(), 1, packed=True)
    elif case == "held":
        with pytest.raises(ValueError, match="held experts"):
            dataclasses.replace(CFG, first_expert_held=14)
    elif case == "dense":
        with pytest.raises(ValueError, match="num_dense_layers"):
            dataclasses.replace(CFG, use_moe=False, expert_bias_rate=0.0)
    elif case == "bias":
        with pytest.raises(ValueError, match="sigmoid router"):
            dataclasses.replace(CFG, moe_score_func="softmax")
    elif case == "keeps":  # a name no layer writes would keep nothing
        with pytest.raises(ValueError, match="remat_keeps names"):
            dataclasses.replace(CFG, remat=True, remat_keeps=("flash_o",))
    else:
        with pytest.raises(ValueError, match="sliding_window"):
            dataclasses.replace(CFG, sliding_window=None)


def test_the_dense_oracle_refuses_the_model():
    tokens, labels = _batch()
    with pytest.raises(ValueError, match="reference_afmoe"):
        transformer.dense_reference_loss(CFG, _weights(CFG), tokens, labels)


# ---- what the configuration states in float32 stays float32 ------------------

def _eqns(jaxpr, scope=""):
    """(equation, its whole scope path) for every equation of ``jaxpr``
    and of the jaxprs inside it (scan, remat, shard_map, pjit bodies)."""
    for eqn in jaxpr.eqns:
        path = scope + "/" + str(eqn.source_info.name_stack)
        yield eqn, path
        for v in eqn.params.values():
            for w in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(w, "jaxpr", w)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, path)


def _types(eqn):
    return [v.aval.dtype for v in eqn.invars if hasattr(v.aval, "dtype")], \
        [v.aval.dtype for v in eqn.outvars]


def _not_float32(jaxpr):
    """By part, the equations of a traced bf16 train step that compute in
    another type than the float32 the configuration states for that part
    (and how many equations of the part were looked at): the router's
    matmul at the highest precision, its scores and its top-k under
    ``moe_route``; every norm's ``rsqrt``; the head's matmul and its
    logits under ``head``; the loss's ``exp`` and ``log`` under ``loss``;
    the bias's rule under ``router_bias``; the experts' gated product
    under ``moe_experts`` and the sum over a token's picks under
    ``moe_combine``, inside the loop over windows where there is one."""
    f32 = jnp.dtype(jnp.float32)
    looked = {part: 0 for part in ("router", "norms", "head", "loss",
                                   "bias", "gated", "token_sums")}
    wrong = {part: [] for part in looked}

    def hold(part, eqn, ok):
        looked[part] += 1
        if not ok:
            wrong[part].append(str(eqn))

    for eqn, path in _eqns(jaxpr):
        name = eqn.primitive.name
        ins, outs = _types(eqn)
        floats = [t for t in ins + outs if jnp.issubdtype(t, jnp.floating)]
        if "moe_route" in path and name in ("dot_general", "logistic",
                                            "top_k"):
            highest = name != "dot_general" or "HIGHEST" in str(
                eqn.params["precision"])
            hold("router", eqn, highest and all(t == f32 for t in floats))
        elif "moe_experts" in path and name == "logistic":
            hold("gated", eqn, ins == [f32])
        elif "moe_combine" in path and name == "reduce_sum" and floats:
            hold("token_sums", eqn, outs == [f32])
        elif name == "rsqrt":
            hold("norms", eqn, ins == [f32])
        elif "/head" in path and name == "dot_general":
            hold("head", eqn, all(t == f32 for t in floats))
        elif "/loss" in path and name in ("exp", "log", "reduce_max"):
            hold("loss", eqn, all(t == f32 for t in floats))
        elif "router_bias" in path and floats:
            hold("bias", eqn, all(t == f32 for t in floats))
    return wrong, looked


def _traced_bf16_step(shape=(B, T), base=CFG):
    cfg = dataclasses.replace(base, dtype=jnp.bfloat16, remat=True,
                              remat_keeps=("flash_out", "attn_q"),
                              max_seq=max(shape[1], CFG.max_seq))
    mesh = _mesh()
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1), cfg,
                          mesh)
    assert params["wg"].dtype == jnp.bfloat16
    assert params["expert_bias"].dtype == params["router"].dtype == \
        params["ln1"].dtype == jnp.float32
    optimizer = optax.adamw(3e-4)
    opt_state = init_opt_state(optimizer, transformer.trained(params), mesh)
    tokens, labels = _batch(shape=shape)
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1)
    return jax.make_jaxpr(step)(params, opt_state, tokens, labels).jaxpr


def test_the_kernels_take_k_and_v_at_their_own_head_count(monkeypatch):
    """Four query heads over one K/V head, sliding and full layers,
    rematerialized: with the kernels (interpreted) no repeat of K or V to
    the query heads' count and no sum over a group is in the step; the
    kernels' XLA twins, which run without them, repeat inside
    themselves."""
    import hlo_text

    cfg = dataclasses.replace(CFG, n_kv_heads=1)
    assert hlo_text.grouped_shapes(B, T, cfg.n_heads, 1, cfg.d_head) & \
        hlo_text.repeats_and_group_sums(_traced_bf16_step(base=cfg))
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    calls = hlo_text.assert_kv_stay_grouped(
        _traced_bf16_step(base=cfg), B, T, cfg.n_heads, 1, cfg.d_head)
    # Two runs of layers with a window and one without, each forward
    # and once more where its layer is rematerialized; one backward each.
    assert len(calls["flash_fwd"]) >= len(calls["flash_bwd"]) >= 2


# 2 x 24 tokens: one window holds every assignment, no loop. 2 x 256: 1,536
# assignments on 16 experts of which 4 are held, windows of 1,024.
STEP_SHAPES = {"plain": (B, T), "windows": (B, 256)}


@pytest.fixture(scope="module", params=sorted(STEP_SHAPES))
def bf16_step_parts(request):
    jaxpr = _traced_bf16_step(STEP_SHAPES[request.param])
    assert _control_flow(jaxpr).count("while") == (request.param == "windows")
    return _not_float32(jaxpr)


@pytest.mark.parametrize("part, at_least", [
    ("router", 3 * 4), ("norms", 5 * 6 + 1), ("head", 1), ("loss", 3),
    ("bias", 1), ("gated", 3 * 3), ("token_sums", 2 * 3)])
def test_a_bf16_step_computes_its_float32_parts_in_float32(
        bf16_step_parts, part, at_least):
    """What the cell's ``correct`` cannot tell apart on the chip (a
    router, a norm or the head one precision lower moves a token's
    cross-entropy by less than the seeds do) is held here, in the traced
    step: the part's operations are there, and every one is float32,
    with and without the loop over windows of the sorted assignments."""
    wrong, looked = bf16_step_parts
    assert looked[part] >= at_least, looked
    assert not wrong[part], wrong[part]


@pytest.mark.parametrize("part", ["router", "norms", "head"])
def test_the_float32_check_sees_a_part_in_bf16(part, monkeypatch):
    bf16 = jnp.bfloat16
    if part == "norms":
        def norm(x, scale, eps):
            v = x.astype(bf16)
            ms = jnp.mean(jnp.square(v), -1, keepdims=True)
            return (v * jax.lax.rsqrt(ms + eps)
                    * scale.astype(bf16)).astype(x.dtype)
        monkeypatch.setattr(transformer, "_rmsnorm", norm)
    else:
        einsum = jnp.einsum
        spec = {"router": "btd,de->bte", "head": "btd,dv->btv"}[part]
        monkeypatch.setattr(jnp, "einsum", lambda s, *ops, **kw: einsum(
            s, *ops, **kw) if s != spec else einsum(
            s, *(a.astype(bf16) for a in ops)).astype(jnp.float32))
    wrong, _ = _not_float32(_traced_bf16_step())
    assert wrong[part] and not any(
        v for k, v in wrong.items() if k != part), wrong
