"""The Mamba-2 / attention hybrid decoder (a layer pattern as data, the
Mamba-2 mixer with its chunked scan, NoPE grouped-query attention with
its own softmax scale, a gated SiLU MLP, a tied head, Granite's four
multipliers) against the benchmark's plain float32 reference
(``benchmark/reference_hybrid.py``: the scan as a recurrence over time),
at small widths on the CPU with seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference_hybrid
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, make_router_load_fn,
    shard_params)
from horovod_tpu.ops import ssd
from horovod_tpu.parallel.mesh import build_parallel_mesh

PATTERN = ("mamba", "mamba", "attention", "mamba")
CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, d_head=8, n_kv_heads=2, d_ff=64,
    n_layers=4, max_seq=64, layer_types=PATTERN, mamba_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk=8, norm="rmsnorm",
    gated_mlp=True, tie_embeddings=True, pos_table=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=1 / 8)
B, T = 2, 24  # three chunks of 8: the carried state is in it


def _weights(cfg, seed=0, n_stages=1):
    """Seeded weights with the scales, ``D`` and the biases away from
    their defaults, so that one applied in the wrong place shows."""
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages=n_stages)
    names = [n for n in ("ln1", "ln2", "final_ln", "m_g", "m_D")
             if n in params]
    for key, name in zip(jax.random.split(jax.random.PRNGKey(seed + 1),
                                          len(names)), names):
        params[name] = 1 + 0.1 * jax.random.normal(key, params[name].shape)
    return params


def _batch(seed=1, vocab=128, shape=(B, T)):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)
    return tokens, jnp.roll(tokens, -1, axis=1)


def _program(cfg, params, tokens, labels, **axes):
    axes = dict(dict(dp=1, pp=1, sp=1, tp=1), **axes)
    mesh = build_parallel_mesh(jax.devices()[:int(np.prod(list(
        axes.values())))], **axes)
    data = NamedSharding(mesh, P("dp", "sp"))
    loss, grads = jax.jit(jax.value_and_grad(make_loss_fn(
        cfg, mesh, n_microbatches=1)))(
        shard_params(params, cfg, mesh), jax.device_put(tokens, data),
        jax.device_put(labels, data))
    return float(loss), jax.device_get(grads)


def _model(cfg):
    return dict(layer_types=cfg.kinds, rms_norm_eps=cfg.norm_eps,
                embedding_multiplier=cfg.embedding_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                attention_multiplier=cfg.attention_multiplier,
                logits_scaling=cfg.logits_scaling)


def _reference(cfg, params, tokens, labels):
    loss, grads = jax.jit(
        lambda p, t, l: reference_hybrid.decoder_hybrid_loss_and_grad(
            p, t, l, _model(cfg)))(params, tokens, labels)
    return float(loss), jax.device_get(grads)


def _worst_leaf(got, want):
    """Largest difference over the reference's largest entry, by leaf."""
    assert set(got) == set(want)
    return {k: float(np.abs(np.asarray(got[k], np.float32)
                            - np.asarray(want[k], np.float32)).max()
                     / np.abs(np.asarray(want[k], np.float32)).max())
            for k in want}


# ---- the scan alone ---------------------------------------------------------

def _scan_inputs(T, dtype=jnp.float32, H=3, Pm=4, N=5, seed=0):
    """One sequence with decays well under one: ``exp(dt A)`` between 0.2
    and 0.9 a token, so a cumulative sum that starts or ends one token
    off, or a carried state without its decay, moves every output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (1, T, H, Pm)).astype(dtype)
    Bm = jax.random.normal(ks[1], (1, T, N)).astype(dtype)
    Cm = jax.random.normal(ks[2], (1, T, N)).astype(dtype)
    dt = jax.random.uniform(ks[3], (1, T, H), jnp.float32, 0.2, 1.0)
    A = -jax.random.uniform(ks[4], (H,), jnp.float32, 0.5, 1.6)
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, Cm, D


def _recurrence(x, dt, A, Bm, Cm, D):
    f32 = lambda a: a.astype(jnp.float32)
    return reference_hybrid.ssd_recurrence(f32(x[0]), dt[0], A, f32(Bm[0]),
                                           f32(Cm[0]), D)[None]


@pytest.mark.parametrize("T", [8, 16, 40, 21], ids=[
    "1-chunk", "2-chunks", "5-chunks", "2.6-chunks-padded"])
def test_chunked_scan_is_the_recurrence_value_and_every_gradient(T):
    args = _scan_inputs(T)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def value(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    chunked = lambda *a: ssd.ssd_chunked(*a, chunk=8)
    np.testing.assert_allclose(chunked(*args), _recurrence(*args),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(value(chunked), argnums=range(6))(*args)
    want = jax.grad(value(_recurrence), argnums=range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=f"gradient by {name}")


@pytest.mark.parametrize("path, chunk, sizes", [
    ("einsums", 16, dict(H=4)), ("kernels", 128, dict(H=4, Pm=64, N=128))])
def test_the_decay_and_score_arrays_are_no_residuals_of_the_scan(
        monkeypatch, path, chunk, sizes):
    """The backward pass keeps the scan's arguments, the chunk states and
    what is made of them, not [H, Q, Q]: the einsum form by
    ``jax.checkpoint`` around the chunks' term, the kernel pair (here
    interpreted, at the least sizes its plan takes) by its
    ``custom_vjp``."""
    if path == "kernels":
        monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    args = _scan_inputs(2 * chunk, **sizes)
    scan = lambda *a: ssd.ssd_chunked(*a, chunk=chunk)
    assert ("pallas_call" in str(jax.make_jaxpr(scan)(*args))) == (
        path == "kernels")
    _, residuals = jax.vjp(scan, *args)
    biggest = max(leaf.size for leaf in jax.tree_util.tree_leaves(residuals))
    assert biggest <= args[0].size < 4 * chunk * chunk * 2


@pytest.mark.parametrize("bf16_sums", [False, True],
                         ids=["as-stated", "decay-sums-in-bf16"])
def test_bf16_operands_hold_a_tolerance_that_bf16_decay_sums_break(
        monkeypatch, bf16_sums):
    """bf16 ``x``, ``B``, ``C`` with float32 ``dt``, decays and sums (as
    the configuration states) stay within 1 % of the float32 recurrence
    on the same rounded inputs, in relative L2; the cumulative sums of
    ``dt A`` in bf16 — one float32 part in the lower precision — do not."""
    args = _scan_inputs(64, jnp.bfloat16, H=4, Pm=8, N=8)
    if bf16_sums:
        cumsum = jnp.cumsum
        monkeypatch.setattr(ssd.jnp, "cumsum", lambda a, axis: cumsum(
            a.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
    # A chunk of its own, so that neither case meets the other's trace.
    got = ssd.ssd_chunked(*args, chunk=32 if bf16_sums else 16).astype(
        jnp.float32)
    want = _recurrence(*args)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert (err > 1e-2) if bf16_sums else (err < 1e-2), err


# ---- the whole model --------------------------------------------------------

def test_loss_and_every_gradient_leaf_match_the_reference_in_float32():
    """Float32 sums of a few dozen terms in two orders: 1e-5 of a leaf's
    largest entry (read 3e-6 at most)."""
    params, (tokens, labels) = _weights(CFG), _batch()
    loss, grads = _program(CFG, params, tokens, labels)
    ref_loss, ref_grads = _reference(CFG, params, tokens, labels)
    assert abs(loss - ref_loss) / ref_loss < 2e-6
    worst = _worst_leaf(grads, ref_grads)
    assert max(worst.values()) < 1e-5, worst


def test_bf16_loss_and_gradients_stay_near_the_float32_reference():
    """bf16 parameters, activations and matmul operands with float32
    norms, decays, head and loss, on the same (bf16-rounded) weights: the
    loss of 256 tokens within 1e-4 (bf16's roundings average out over
    tokens; read 2e-6), every gradient leaf within 8 % in relative L2
    (there they do not; read 0.9 to 3.2 %). What a float32 part in bf16 does is
    held where it shows, on the scan alone, above."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda a, like: a.astype(like.dtype), _weights(cfg),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), 1)))
    tokens, labels = _batch(shape=(4, 64))
    loss, grads = _program(cfg, params, tokens, labels)
    ref_loss, ref_grads = _reference(cfg, params, tokens, labels)
    assert abs(loss - ref_loss) / ref_loss < 1e-4
    far = {k: float(np.linalg.norm(np.asarray(grads[k], np.float32)
                                   - ref_grads[k].astype(np.float32))
                    / np.linalg.norm(ref_grads[k].astype(np.float32)))
           for k in ref_grads}
    assert max(far.values()) < 8e-2, far


def _token_nll(cfg, params, tokens, labels):
    """Every token's cross-entropy by the program's forward pass."""
    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    forward = transformer.make_forward_fn(cfg, mesh, n_microbatches=1)
    return transformer.token_nll(
        forward(shard_params(params, cfg, mesh), tokens), labels)


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-5),
                                          (jnp.bfloat16, 1e-3)],
                         ids=["float32", "bf16"])
def test_every_tokens_cross_entropy_matches_the_references(dtype, limit):
    """What the benchmark's cell holds beside the loss
    (``decoder_hybrid.NLL_RMS_TOL``): the program's forward pass and its
    ``token_nll`` against the reference's cross-entropy of every token,
    as the root of the mean squared difference. Read 2e-6 in float32 and
    1.7e-4 in bf16 on the same (bf16-rounded) weights. At these widths a
    float32 part in bf16 hides under the operands' own rounding; at the
    published ones it does not (``benchmark/limit_check_hybrid.py``)."""
    cfg = dataclasses.replace(CFG, dtype=dtype, mamba_chunk=64, max_seq=256)
    params = jax.tree_util.tree_map(
        lambda a, like: a.astype(like.dtype), _weights(cfg),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), 1)))
    tokens, labels = _batch(shape=(2, 256))
    want = jax.jit(lambda p, t, l: reference_hybrid.token_nll(
        p, t, l, _model(cfg)))(params, tokens, labels)
    got = _token_nll(cfg, params, tokens, labels)
    assert got.shape == want.shape == tokens.shape
    assert float(jnp.sqrt(jnp.mean(jnp.square(got - want)))) < limit


def test_a_rematerialized_layer_gives_the_same_and_keeps_what_it_names():
    """``remat``: the same loss and gradients, and of a layer's forward
    pass the backward keeps the two named arrays of a Mamba layer and
    nothing of the MLP's width."""
    params, (tokens, labels) = _weights(CFG), _batch()
    want_loss, want = _program(CFG, params, tokens, labels)
    cfg = dataclasses.replace(CFG, remat=True)
    loss, grads = _program(cfg, params, tokens, labels)
    assert loss == want_loss
    assert max(_worst_leaf(grads, want).values()) < 1e-6
    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    _, backward = jax.vjp(make_loss_fn(cfg, mesh, n_microbatches=1),
                          shard_params(params, cfg, mesh), tokens, labels)
    kept = [leaf.shape for leaf in jax.tree_util.tree_leaves(backward)
            if T in leaf.shape]  # activations, not parameters
    inner = (CFG.mamba_heads, CFG.mamba_d_head)
    # z and x, and the scan's output, stacked over each run of Mamba
    # layers (two runs: layers 0-1 and 3).
    assert sorted(s[s.index(T) + 1:] for s in kept if s[-2:] == inner) == \
        [(2,) + inner, (2,) + inner, inner, inner]
    assert not any(CFG.d_ff in s for s in kept)


def test_the_tied_tables_gradient_is_the_sum_of_both_ends():
    params, (tokens, labels) = _weights(CFG), _batch()
    _, tied = _program(CFG, params, tokens, labels)
    untied = dataclasses.replace(CFG, tie_embeddings=False)
    _, both = _program(untied, dict(params, head=params["embed"].T),
                       tokens, labels)
    assert np.abs(both["head"]).max() > 0 and np.abs(both["embed"]).max() > 0
    np.testing.assert_allclose(tied["embed"], both["embed"] + both["head"].T,
                               rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("name, other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", 2.0)])
def test_each_multiplier_moves_the_loss_as_it_moves_the_references(name,
                                                                   other):
    params, (tokens, labels) = _weights(CFG), _batch()
    base, _ = _program(CFG, params, tokens, labels)
    cfg = dataclasses.replace(CFG, **{name: other})
    loss, _ = _program(cfg, params, tokens, labels)
    ref_loss, _ = _reference(cfg, params, tokens, labels)
    assert abs(loss - base) / base > 2e-5  # ten times the agreement
    assert abs(loss - ref_loss) / ref_loss < 2e-6


def test_no_position_table_and_no_head_leaf():
    params = init_params(CFG, jax.random.PRNGKey(0), 1)
    assert "pos" not in params and "head" not in params
    assert set(params) == set(transformer._param_specs(CFG))
    assert params["wq"].shape[:2] == (1, 1)      # one attention layer
    assert params["m_wzx"].shape[:2] == (1, 3)   # three Mamba layers
    assert params["wgu"].shape[:2] == (1, 4)     # every layer


@pytest.mark.parametrize("kinds, want", [
    (("attention",) * 3, [("attention", 0, 0, 3)]),
    (PATTERN, [("mamba", 0, 0, 2), ("attention", 2, 0, 1),
               ("mamba", 3, 2, 1)]),
    (("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
     [("mamba", 0, 0, 5), ("attention", 5, 0, 1), ("mamba", 6, 5, 4)])])
def test_the_stage_walks_the_patterns_maximal_runs(kinds, want):
    """(mixer, first layer, earlier layers of its kind, length); every
    layer ends in the dense MLP, whose stack has a row a layer."""
    runs = transformer._runs([(kind, "mlp") for kind in kinds])
    assert [(kind, rows[None], rows[kind], n)
            for kind, _, rows, n in runs] == want
    assert all(ffn == "mlp" and rows["mlp"] == rows[None]
               for _, ffn, rows, _ in runs)


@pytest.mark.parametrize("base", [
    TransformerConfig(n_layers=3),
    TransformerConfig(n_layers=2, use_moe=True, moe_top_k=2, norm="rmsnorm",
                      qk_norm=True, rope=True)], ids=["dense", "moe"])
def test_a_pattern_of_one_kind_lowers_to_the_text_of_no_pattern(base):
    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    tokens, labels = _batch(vocab=base.vocab, shape=(2, 16))

    def text(cfg):
        params = init_params(cfg, jax.random.PRNGKey(0), 1)
        return jax.jit(jax.value_and_grad(make_loss_fn(
            cfg, mesh, n_microbatches=1))).lower(
            params, tokens, labels).as_text()

    assert text(base) == text(dataclasses.replace(
        base, layer_types=("attention",) * base.n_layers))


def test_the_kernels_take_k_and_v_at_their_own_head_count(monkeypatch):
    """The hybrid's one attention layer, four query heads over one K/V
    head: with the kernels (interpreted) the loss and its gradient make
    no copy of K or V at the query heads' count; the XLA twins do."""
    import hlo_text

    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    cfg = dataclasses.replace(CFG, remat=True, n_kv_heads=1)
    tokens, labels = _batch()

    def traced():
        return jax.make_jaxpr(jax.value_and_grad(make_loss_fn(
            cfg, mesh, n_microbatches=1)))(
            init_params(cfg, jax.random.PRNGKey(0), 1), tokens,
            labels).jaxpr

    assert hlo_text.grouped_shapes(B, T, cfg.n_heads, 1, cfg.d_head) & \
        hlo_text.repeats_and_group_sums(traced())
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    hlo_text.assert_kv_stay_grouped(traced(), B, T, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.d_head)


@pytest.mark.parametrize("axes, kinds", [
    (dict(dp=2, tp=2), PATTERN),
    (dict(pp=2), ("mamba", "attention") * 2)], ids=["dp2-tp2", "pp2"])
def test_sharded_layouts_give_what_one_device_gives(axes, kinds):
    cfg = dataclasses.replace(CFG, layer_types=kinds)
    stages = axes.get("pp", 1)
    staged = _weights(cfg, n_stages=stages)
    flat = {k: v if v.ndim < 2 or k == "embed"
            else v.reshape((1, -1) + v.shape[2:]) for k, v in staged.items()}
    tokens, labels = _batch()
    want_loss, want = _program(cfg, flat, tokens, labels)
    loss, grads = _program(cfg, staged, tokens, labels, **axes)
    assert abs(loss - want_loss) / want_loss < 1e-6
    grads = {k: v.reshape(want[k].shape) for k, v in grads.items()}
    worst = _worst_leaf(grads, want)
    assert max(worst.values()) < 1e-5, worst


@pytest.mark.parametrize("case", ["sp", "segment_ids", "pp"])
def test_what_a_mamba_layer_cannot_do_yet_raises(case):
    if case == "segment_ids":
        mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
        with pytest.raises(ValueError, match="segment boundary"):
            make_loss_fn(CFG, mesh, n_microbatches=1, packed=True)
        return
    mesh = build_parallel_mesh(jax.devices()[:2], dp=1, **{
        **dict(pp=1, sp=1, tp=1), case: 2})
    match = {"sp": "cannot run over sp", "pp": "whole periods"}[case]
    with pytest.raises(ValueError, match=match):
        make_loss_fn(CFG, mesh, n_microbatches=1)


def test_an_expert_layer_after_either_mixer_counts_every_assignment():
    """The run that does not start the stage hands the expert kernels
    its layers' places in the stage's stacks."""
    cfg = dataclasses.replace(
        CFG, layer_types=("mamba", "attention", "attention"), n_layers=3,
        use_moe=True, n_experts=4, d_expert=16, moe_top_k=2,
        gated_mlp=False)
    mesh = build_parallel_mesh(jax.devices()[:1], dp=1, pp=1, sp=1, tp=1)
    params = shard_params(_weights(cfg), cfg, mesh)
    tokens, labels = _batch()
    load = np.asarray(make_router_load_fn(cfg, mesh, 1)(params, tokens))
    assert load.shape == (3, 4) and (load.sum(axis=1) == 2 * B * T).all()
    loss = jax.jit(make_loss_fn(cfg, mesh, 1))(params, tokens, labels)
    assert np.isfinite(float(loss))


def test_the_dense_oracle_refuses_the_hybrid():
    tokens, labels = _batch()
    with pytest.raises(ValueError, match="reference_hybrid"):
        transformer.dense_reference_loss(CFG, _weights(CFG), tokens, labels)
