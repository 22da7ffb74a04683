"""The EvaByte decoder on the CPU at a tiny size (d 64, 4 heads of 16,
windows of 32, chunks of 4, 128 tokens): the mixer, the loss over eight
heads and their gradients against ``benchmark/reference_eva.py`` on seeded
weights, through the XLA twins and through the interpreted kernels; the
two identities (chunks of one token, or a window that holds the sequence,
give causal softmax attention); the kernels' block-causal rule against the
XLA path, forward and ``flash_bwd``, at offsets and blocks that divide no
tile; what the kind refuses; the heads' labels; the float32 stream and the
unit-offset norm."""

import dataclasses

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference_eva
from horovod_tpu import metrics
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_train_step,
    shard_params)
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.eva_attention import eva_attention
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

T, W, C, HEADS = 128, 32, 4, 8
CFG = TransformerConfig(
    vocab=40, d_model=64, n_heads=4, d_head=16, d_ff=96, n_layers=2,
    max_seq=T, layer_types=("eva",) * 2, eva_window=W, eva_chunk=C,
    n_pred_heads=HEADS, rope_theta=1e5, pos_table=False, norm="rmsnorm",
    norm_unit_offset=True, float32_stream=True, gated_mlp=True)
MODEL = dict(num_hidden_layers=2, rms_norm_eps=1e-5, rope_theta=1e5,
             window_size=W, chunk_size=C, num_pred_heads=HEADS)


def _mesh(**axes):
    n = int(np.prod(list(axes.values()) or [1]))
    return build_parallel_mesh(jax.devices()[:n], **{
        "sp": 1, "tp": 1, "pp": 1, **axes})


def _inputs(cfg=CFG, seed=0, batch=2, pp=1):
    k_params, k_tokens, k_norms = jax.random.split(jax.random.PRNGKey(seed),
                                                   3)
    params = init_params(cfg, k_params, pp)
    # The norms' weights start at zero: moved off it, so that the unit
    # offset and their gradients are seen.
    for salt, name in enumerate(("ln1", "ln2", "final_ln")):
        params[name] = 0.1 * jax.random.normal(
            jax.random.fold_in(k_norms, salt), params[name].shape)
    tokens = jax.random.randint(k_tokens, (batch, T), 0, cfg.vocab,
                                jnp.int32)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _program(cfg, params, tokens, labels, mesh=None):
    mesh = mesh or _mesh()
    data = NamedSharding(mesh, P("dp", "sp"))
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=1, with_readings=True)
    with jax.default_matmul_precision("highest"):
        (loss, readings), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(
                shard_params(params, cfg, mesh),
                jax.device_put(tokens, data), jax.device_put(labels, data))
    return loss, readings, grads


@pytest.mark.parametrize("kernels", ["xla", "interpreted"])
def test_loss_and_gradients_against_the_reference(kernels, monkeypatch):
    if kernels == "interpreted":
        monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    params, tokens, labels = _inputs()
    loss, readings, grads = _program(CFG, params, tokens, labels)
    want_loss, want = reference_eva.loss_and_grad(params, tokens, labels,
                                                  MODEL)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    want_nll = reference_eva.forward(params, tokens, labels, MODEL)
    assert readings["token_nll"].shape == (2, T, HEADS)
    np.testing.assert_allclose(readings["token_nll"], want_nll, atol=2e-5)
    assert set(grads) == set(want)
    for name in sorted(want):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(grads[name]) / scale, np.asarray(want[name]) / scale,
            atol=2e-5, err_msg=name)


def _dense_eva(q, k, v, k_sum, v_sum, window, chunk):
    """One softmax over a window's own keys and the earlier windows'
    summaries, dense."""
    D = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    r = jnp.einsum("bthd,bchd->bhtc", q, k_sum) / np.sqrt(D)
    i = jnp.arange(q.shape[1])[:, None]
    m = jnp.arange(k.shape[1])[None, :]
    c = jnp.arange(k_sum.shape[1])[None, :]
    s = jnp.where((m <= i) & (m // window == i // window), s, -jnp.inf)
    r = jnp.where(c // (window // chunk) < i // window, r, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, r], -1), -1)
    return (jnp.einsum("bhts,bshd->bthd", p[..., :k.shape[1]], v)
            + jnp.einsum("bhtc,bchd->bthd", p[..., k.shape[1]:], v_sum))


def _qkv(seed=0, B=2, H=2, D=16, t=T):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, t, H, D), jnp.float32)
                 for _ in range(3))


# (B, H, window): two heads over four windows; H != n and B > 1, which a
# wrong row order ((b H + h) n + w against (b n + w) H + h) cannot pass;
# one window, where no summary is seen and one call runs.
SHAPES = {"2x2-4windows": (2, 2, W), "2x3-4windows": (2, 3, W),
          "2x3-1window": (2, 3, T)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernels", ["xla", "interpreted"])
def test_attention_and_its_gradients_against_dense(kernels, shape,
                                                   monkeypatch):
    if kernels == "interpreted":
        monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    B, H, window = SHAPES[shape]
    q, k, v = _qkv(B=B, H=H)
    k_sum, v_sum = (x[:, ::C] * 0.7 for x in (k, v))  # any summaries
    w = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)

    def loss(fn, *args):
        return jnp.sum(fn(*args, window, C) * w)

    got = jax.grad(lambda *a: loss(eva_attention, *a), argnums=range(5))(
        q, k, v, k_sum, v_sum)
    want = jax.grad(lambda *a: loss(_dense_eva, *a), argnums=range(5))(
        q, k, v, k_sum, v_sum)
    np.testing.assert_allclose(
        eva_attention(q, k, v, k_sum, v_sum, window, C),
        _dense_eva(q, k, v, k_sum, v_sum, window, C), atol=2e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=5e-5)


def _head_major_transposes(jaxpr, like):
    """The ``transpose`` equations of a traced program that give the
    head-major form of an array of ``like``'s logical shape and type
    ([B, T, H, D] -> [B, H, T, D])."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            aval = eqn.invars[0].aval if eqn.invars else None
            if (eqn.primitive.name == "transpose"
                    and eqn.params["permutation"] == (0, 2, 1, 3)
                    and aval.shape == like.shape
                    and aval.dtype == like.dtype):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("window, passes", [(W, 2), (T, 0)],
                         ids=["4windows", "1window"])
def test_the_heads_are_merged_once_a_pass(window, passes):
    """A traced forward asks for the head-major form of q, k and v once
    each and a traced backward for those and dO's, whichever key sets read
    them (cut into windows before the heads were merged, q and dO were
    transposed once a call: 4 forward, 6 backward); and the counter reads
    the two differentiated passes of a sequence of several windows."""
    q = jax.ShapeDtypeStruct((2, T, 3, 16), jnp.bfloat16)
    s = jax.ShapeDtypeStruct((2, T // C, 3, 16), jnp.bfloat16)

    def attend(*a):
        return eva_attention(*a, window, C)

    forward = jax.make_jaxpr(attend)(q, q, q, s, s)
    assert len(_head_major_transposes(forward, q)) == 3
    before = metrics()["python"].get("kernels.eva.merged_operands", 0)
    both = jax.make_jaxpr(jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        argnums=range(5)))(q, q, q, s, s)
    assert len(_head_major_transposes(both, q)) == 3 + 4
    assert metrics()["python"].get("kernels.eva.merged_operands",
                                   0) - before == passes


def test_refuses_a_grouped_key_side():
    """The windows are cut from the head-major rows, an order the kernels'
    grouped K side cannot follow: fewer K/V heads are refused here, as the
    configuration refuses ``n_kv_heads``."""
    q, k, v = _qkv(H=4)
    with pytest.raises(ValueError, match="its own key and value"):
        eva_attention(q, k[:, :, :2], v[:, :, :2], k[:, ::C, :2],
                      v[:, ::C, :2], W, C)


@pytest.mark.parametrize("window, chunk", [(W, 1), (T, C), (4 * T, C)])
def test_the_identities(window, chunk):
    """Chunks of one token are the keys and values themselves; a window
    that holds the sequence has no summary: causal softmax attention."""
    cfg = dataclasses.replace(CFG, eva_window=window, eva_chunk=chunk)
    h = jnp.asarray(np.random.RandomState(1).randn(2, T, cfg.d_model),
                    jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(3), 1)
    lp = {name: params[name][0, 0] for name in ("e_wqkv", "e_mu", "e_phi",
                                                "e_wo")}
    got = transformer._eva_mixer(cfg, h, lp)
    qkv = jnp.einsum("btd,dchk->cbthk", h, lp["e_wqkv"])
    pos = jnp.arange(T)
    attn = pa.flash_attention(
        transformer._rope(qkv[0], pos, cfg.rope_theta),
        transformer._rope(qkv[1], pos, cfg.rope_theta), qkv[2], causal=True)
    want = jnp.einsum("bthk,hkd->btd", attn, lp["e_wo"])
    np.testing.assert_allclose(got, want, atol=1e-5)


# --- the kernels' block-causal rule

def _dense_blocks(q, k, v, q_off, k_off, blocks):
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    i = (jnp.arange(q.shape[1]) + q_off)[:, None]
    j = (jnp.arange(k.shape[1]) + k_off)[None, :]
    keep = j // blocks[1] < i // blocks[0]
    s = jnp.where(keep, s, -1e30)  # a row of the first block sees none
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    return jnp.einsum("bhts,bshd->bthd", p / jnp.maximum(l, 1e-30), v)


RULES = [  # (Tq, Tk, q_off, k_off, (q_block, k_block), tile cap)
    (64, 64, 0, 0, (16, 16), 16),     # equal blocks that are the tile
    (64, 16, 0, 0, (16, 4), 8),       # summaries: four a block of 16
    (96, 48, 24, 0, (24, 12), 16),    # blocks that divide no tile, offset
    (64, 64, 40, 8, (20, 7), 16),     # nothing divides anything
    (64, 64, 0, 0, (16, 16), None),   # one tile holds every block
]


@pytest.mark.parametrize("Tq, Tk, q_off, k_off, blocks, cap", RULES)
def test_block_causal_kernels_against_the_xla_path(Tq, Tk, q_off, k_off,
                                                   blocks, cap, monkeypatch):
    """Forward (the state, the plain output) and the fused backward under
    the rule, interpreted, against the XLA twins and a dense oracle: the
    walk enters every tile some query may see and the mask hides the rest
    of it."""
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    if cap:
        monkeypatch.setattr(pa, "_TILE_CAP", cap)
    q = _qkv(1, t=Tq)[0]
    _, k, v = _qkv(2, t=Tk)
    do = _qkv(3, t=Tq)[1]
    before = metrics()["python"]
    dense = _dense_blocks(q, k, v, q_off, k_off, blocks)
    out = {how: pa.flash_attention(q, k, v, causal=True, q_off=q_off,
                                   k_off=k_off, blocks=blocks,
                                   use_pallas=how)
           for how in (True, False)}
    np.testing.assert_allclose(out[False], dense, atol=2e-5)
    np.testing.assert_allclose(out[True], dense, atol=2e-5)
    states = {how: pa.flash_attention_block(
        q, k, v, q_off, k_off, causal=True, blocks=blocks, use_pallas=how)
        for how in (True, False)}
    for acc, m, l in states.values():
        o = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        np.testing.assert_allclose(o, dense, atol=2e-5)
    lse = pa.row_lse(*states[False][1:])
    delta = jnp.sum(do * dense, -1).transpose(0, 2, 1)
    grads = {how: pa.flash_attention_block_grads(
        q, k, v, do, lse, delta, q_off, k_off, causal=True, blocks=blocks,
        use_pallas=how) for how in (True, False)}
    want = jax.vjp(lambda *a: _dense_blocks(*a, q_off, k_off, blocks),
                   q, k, v)[1](do)
    for got, twin, ref in zip(grads[True], grads[False], want):
        np.testing.assert_allclose(twin, ref, atol=5e-5)
        np.testing.assert_allclose(got, ref, atol=5e-5)
    after = metrics()["python"]
    for kind in ("fwd", "bwd"):
        name = f"kernels.blockcausal.flash_{kind}"
        assert after.get(name, 0) > before.get(name, 0), name


@pytest.mark.parametrize("kind", ["fwd", "bwd"])
def test_the_summaries_walk_only_visible_tiles(kind):
    """The timed size: 32,768 queries over 2,048 summaries a head, blocks
    of 2,048 over 128. Of the 256 tiles of 512 x 512 the walk enters the
    144 that hold a visible pair (37.7 M pairs for the 31.5 M visible;
    67.1 M in all), forward and transposed alike."""
    rule = pa.BlockCausal(2048, 128)
    plan = pa.kernel_plan(32, 32768, 2048, 128, jnp.bfloat16, True, rule,
                          kind=kind, state=kind == "fwd")
    assert (plan.tile_q, plan.tile_k) == (512, 512)
    assert plan.tiles_visited == 144
    visible = sum(2048 * 128 * w for w in range(16))
    assert visible == 31_457_280 and 144 * 512 * 512 == 37_748_736
    whole = pa.kernel_plan(32, 32768, 2048, 128, jnp.bfloat16, False,
                           kind=kind, state=kind == "fwd")
    assert whole.tiles_visited == 256


def test_the_rule_excludes_a_window():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="a mask rule of its own"):
        pa.flash_attention(q, k, v, causal=True, window=8, blocks=(8, 8))
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, causal=False, blocks=(8, 8))


# --- what the kind refuses, checks and counts

@pytest.mark.parametrize("how, sentence", [
    (dict(packed=True), "packed documents through an eva layer"),
    (dict(sp=2), r"sequence shards \(sp > 1\) through an eva layer"),
    (dict(pp=2), r"pipeline stages \(pp > 1\) through an eva layer")])
def test_refuses_with_its_sentence(how, sentence):
    packed = how.pop("packed", False)
    with pytest.raises(ValueError, match=sentence):
        make_train_step(CFG, optax.adamw(3e-4), _mesh(**how),
                        n_microbatches=1, packed=packed)


@pytest.mark.parametrize("sizes, sentence", [
    (dict(eva_chunk=0), "whole chunks"),
    (dict(eva_window=30), "whole chunks"),
    (dict(n_kv_heads=2), "every head its own key and value"),
    (dict(qk_norm=True), "every head its own key and value"),
    (dict(tie_embeddings=True), "n_pred_heads counts"),
    (dict(head_block=16), "n_pred_heads counts"),
    (dict(norm="layernorm"), "RMSNorm's and the plain residual's"),
    (dict(residual_scales=True), "RMSNorm's and the plain residual's")])
def test_checks_its_configuration(sizes, sentence):
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(CFG, **sizes)


def test_a_sequence_must_be_whole_windows():
    cfg = dataclasses.replace(CFG, eva_window=48, eva_chunk=4)
    params, tokens, labels = _inputs(cfg)
    with pytest.raises(ValueError, match="whole windows of 48"):
        _program(cfg, params, tokens, labels)


@pytest.mark.parametrize("sp_or_packed, sentence", [
    ("packed", "packed documents under n_pred_heads > 1"),
    ("sp", "n_pred_heads > 1 is not built over sp > 1")])
def test_prediction_heads_refuse_what_shifts_cannot_cross(sp_or_packed,
                                                          sentence):
    cfg = dataclasses.replace(CFG, layer_types=None, rope=True,
                              eva_window=0, eva_chunk=0)
    with pytest.raises(ValueError, match=sentence):
        transformer._refuse(cfg, sp_or_packed)


@pytest.mark.parametrize("head", range(HEADS))
def test_a_heads_labels_are_the_bytes_shifted_by_one_more(head):
    """Head j's cross-entropy is the one-head model's whose labels are the
    bytes ``1 + j`` ahead and whose matrix is head j's columns."""
    params, tokens, labels = _inputs()
    _, readings, _ = _program(CFG, params, tokens, labels)
    V = CFG.vocab
    one = dataclasses.replace(CFG, n_pred_heads=1)
    alone = dict(params, head=params["head"][:, head * V:(head + 1) * V])
    _, want, _ = _program(one, alone, tokens,
                          jnp.roll(tokens, -(1 + head), axis=1))
    np.testing.assert_allclose(readings["token_nll"][..., head],
                               want["token_nll"], atol=1e-6)
    np.testing.assert_array_equal(
        reference_eva.head_labels(labels, HEADS)[..., head],
        jnp.roll(tokens, -(1 + head), axis=1))


def test_the_stream_is_float32_and_the_blocks_the_models_type():
    """A bf16 model's traced step: the layer scan carries float32, every
    matmul of a block takes bf16 operands, the poolings, the softmax
    statistics and the head's logits are float32."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, remat=True)
    params = jax.eval_shape(lambda k: init_params(cfg, k, 1),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, T), jnp.int32)
    jaxpr = jax.make_jaxpr(make_loss_fn(cfg, _mesh(), n_microbatches=1))(
        params, tokens, tokens)
    scans, dots, softmaxes = [], [], []

    def walk(jaxpr, scopes=()):
        for eqn in jaxpr.eqns:
            here = scopes + tuple(str(eqn.source_info.name_stack).split("/"))
            if eqn.primitive.name == "scan":
                scans.append([v.aval.dtype for v in eqn.invars[
                    eqn.params["num_consts"]:eqn.params["num_consts"]
                    + eqn.params["num_carry"]]])
            if eqn.primitive.name == "dot_general":
                dots.append((here, [v.aval.dtype for v in eqn.invars]))
            if eqn.primitive.name == "exp":
                softmaxes.append((here, eqn.invars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr.jaxpr)
    assert [jnp.dtype(jnp.float32)] in scans
    in_blocks = [types for scope, types in dots
                 if {"eva_qkv", "eva_out", "mlp"} & set(scope)]
    assert len(in_blocks) >= 4
    assert all(t == jnp.bfloat16 for types in in_blocks for t in types)
    in_head = [types for scope, types in dots if "head" in scope]
    assert in_head and all(t == jnp.float32 for types in in_head
                           for t in types)
    assert softmaxes and all(t == jnp.float32 for _, t in softmaxes)


def test_the_unit_offset_norm():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 64), jnp.float32)
    g = jnp.asarray(np.random.RandomState(1).randn(64) * 0.1, jnp.float32)
    got = transformer._block_norm(CFG)(x, g)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * (1 + g)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    params = init_params(CFG, jax.random.PRNGKey(0), 1)
    assert all(float(jnp.abs(params[name]).max()) == 0.0
               for name in ("ln1", "ln2", "final_ln"))
    assert params["head"].shape == (64, HEADS * CFG.vocab)
    assert params["e_mu"].dtype == params["e_phi"].dtype == jnp.float32


def test_the_train_step_returns_its_readings_and_counts_its_layers():
    mesh = _mesh()
    params, tokens, labels = _inputs()
    optimizer = optax.adamw(3e-4)
    sharded = shard_params(params, CFG, mesh)
    state = init_opt_state(optimizer, sharded, mesh)
    before = metrics()["python"]
    step = make_train_step(CFG, optimizer, mesh, n_microbatches=1,
                           with_readings=True)
    after = metrics()["python"]
    assert after.get("model.eva_layers", 0) - before.get(
        "model.eva_layers", 0) == 2
    assert after.get("model.pred_heads", 0) - before.get(
        "model.pred_heads", 0) == HEADS
    losses = []
    for _ in range(3):
        sharded, state, loss, readings = step(sharded, state, tokens, labels)
        losses.append(float(loss))
    assert readings["token_nll"].shape == (2, T, HEADS)
    assert set(readings) == {"token_nll"}
    assert float(jnp.mean(readings["token_nll"])) == pytest.approx(
        losses[-1], rel=1e-6)
    assert losses[-1] < losses[0]
    assert "eva" in str(step.lower(sharded, state, tokens, labels).as_text(
        debug_info=True))
