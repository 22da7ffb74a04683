"""Sharded-transformer correctness: loss and gradients vs the dense oracle,
across mesh factorings that exercise each parallel axis."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, dense_reference_loss, init_params, make_loss_fn,
    make_train_step, shard_params)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state


def _setup(cfg, mesh, seed=0):
    n_stages = mesh.shape["pp"]
    params = init_params(cfg, jax.random.PRNGKey(seed), n_stages)
    rng = np.random.RandomState(seed)
    B = 4 * mesh.shape["dp"]
    T = 8 * mesh.shape["sp"]
    tokens = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)
    return params, jnp.asarray(tokens), jnp.asarray(labels)


MESHES = [
    dict(dp=2, pp=2, sp=1, tp=2),
    dict(dp=2, pp=2, sp=2, tp=1),
    dict(dp=1, pp=2, sp=2, tp=2),
]


@pytest.mark.parametrize("sizes", MESHES)
def test_loss_matches_dense(sizes):
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64)
    mesh = build_parallel_mesh(jax.devices(), **sizes)
    params, tokens, labels = _setup(cfg, mesh)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    loss = float(jax.jit(loss_fn)(sharded, tok_s, lab_s))
    expected = float(dense_reference_loss(cfg, params, tokens, labels))
    assert loss == pytest.approx(expected, rel=1e-4)


def test_indivisible_heads_raise_descriptive_error():
    """n_heads / kv_heads not divisible by the tp axis must fail fast
    with a named error at shard_params/make_loss_fn — not as an opaque
    XLA sharding error at compile time (round-4 advisor finding)."""
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=1, sp=1, tp=4)
    # 6 query heads over tp=4: indivisible.
    cfg = TransformerConfig(vocab=64, d_model=48, n_heads=6, d_head=8,
                            d_ff=64, n_layers=2, max_seq=64)
    params = init_params(cfg, jax.random.PRNGKey(0), 1)
    with pytest.raises(ValueError, match="n_heads.*tp"):
        shard_params(params, cfg, mesh)
    with pytest.raises(ValueError, match="n_heads.*tp"):
        make_loss_fn(cfg, mesh)
    # 8 query heads but 2 KV heads over tp=4: GQA KV split indivisible.
    cfg = TransformerConfig(vocab=64, d_model=64, n_heads=8, d_head=8,
                            d_ff=64, n_layers=2, max_seq=64, n_kv_heads=2)
    params = init_params(cfg, jax.random.PRNGKey(0), 1)
    with pytest.raises(ValueError, match="kv_heads.*tp"):
        shard_params(params, cfg, mesh)


@pytest.mark.parametrize("sizes", MESHES)
def test_grads_match_dense(sizes):
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64)
    mesh = build_parallel_mesh(jax.devices(), **sizes)
    params, tokens, labels = _setup(cfg, mesh)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)

    grads = jax.jit(jax.grad(loss_fn))(sharded, tok_s, lab_s)
    ref_grads = jax.grad(
        lambda p: dense_reference_loss(cfg, p, tokens, labels))(params)

    for key in ("embed", "head", "final_ln", "wqkv", "wo", "w1", "w2",
                "ln1", "ln2", "pos"):
        got = np.asarray(jax.device_get(grads[key]))
        want = np.asarray(ref_grads[key])
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=1e-5,
            err_msg=f"grad mismatch for {key} with mesh {sizes}")


@pytest.mark.parametrize("sizes", [dict(dp=2, pp=2, sp=2, tp=1),
                                   dict(dp=1, pp=2, sp=2, tp=2)])
def test_ulysses_strategy_matches_dense(sizes):
    # Same function class as the ring strategy, different collective
    # plan: the sp axis re-shards heads via all_to_all. With tp=2 the
    # 4 heads are already head-sharded to 2 locals, which sp=2 then
    # divides — the composed tp x sp head constraint.
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64,
                            sp_strategy="ulysses")
    mesh = build_parallel_mesh(jax.devices(), **sizes)
    params, tokens, labels = _setup(cfg, mesh)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    loss = float(jax.jit(loss_fn)(sharded, tok_s, lab_s))
    expected = float(dense_reference_loss(cfg, params, tokens, labels))
    assert loss == pytest.approx(expected, rel=1e-4)

    grads = jax.jit(jax.grad(loss_fn))(sharded, tok_s, lab_s)
    ref_grads = jax.grad(
        lambda p: dense_reference_loss(cfg, p, tokens, labels))(params)
    for key in ("embed", "wqkv", "wo", "head"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(grads[key])),
            np.asarray(ref_grads[key]), rtol=5e-3, atol=1e-5,
            err_msg=f"ulysses grad mismatch for {key} with mesh {sizes}")


def test_init_opt_state_tolerates_host_leaves():
    # zero_axis partitioning must pass genuinely host-side state leaves
    # (custom transforms keeping numpy tables) through untouched instead
    # of crashing on the missing .sharding; ordinary jnp moments built
    # from numpy params still get partitioned.
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=1, tp=2)

    table = np.ones((4, 4), np.float32)
    custom = optax.GradientTransformation(
        init=lambda p: {"table": table},
        update=lambda g, s, p=None: (g, s))
    state = init_opt_state(custom, {"w": np.ones((8, 4), np.float32)},
                           mesh, zero_axis="dp")
    assert state["table"] is table

    adam = init_opt_state(optax.adam(1e-2),
                          {"w": np.ones((8, 4), np.float32)},
                          mesh, zero_axis="dp")
    assert "dp" in list(adam[0].mu["w"].sharding.spec)


def test_zero_over_dp_composes_with_model_parallelism():
    # ZeRO-1 for the model-parallel path: moments sharded over dp ON TOP
    # of the params' pp/tp sharding, pinned by opt_shardings in the
    # compiled step. The math must not change; the memory must.
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=1, tp=2)
    params, tokens, labels = _setup(cfg, mesh)
    sharded = shard_params(params, cfg, mesh)
    optimizer = optax.adam(1e-2)
    opt_state = init_opt_state(optimizer, sharded, mesh, zero_axis="dp")
    opt_shardings = jax.tree_util.tree_map(lambda x: x.sharding, opt_state)

    # Moment leaves carry dp on top of the param's axes, and each
    # device's addressable shard is half the leaf (dp=2).
    mu = opt_state[0].mu
    assert "dp" in jax.tree_util.tree_leaves(
        [list(mu["wqkv"].sharding.spec)])
    assert "pp" in list(mu["wqkv"].sharding.spec)
    full = int(np.prod(mu["embed"].shape))
    local = int(np.prod(mu["embed"].addressable_shards[0].data.shape))
    assert local * 2 <= full, (local, full)

    step = make_train_step(cfg, optimizer, mesh, n_microbatches=2,
                           opt_shardings=opt_shardings)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)

    # Baseline: same model, un-partitioned optimizer state.
    base_opt_state = init_opt_state(optimizer, sharded, mesh)
    base_step = make_train_step(cfg, optimizer, mesh, n_microbatches=2)

    # Fresh param buffers for the baseline: the zero step donates its
    # inputs, and device_put may alias the host-side source arrays.
    sharded_b = shard_params(init_params(cfg, jax.random.PRNGKey(0), 2),
                             cfg, mesh)
    p_z, o_z, l_z = step(sharded, opt_state, tok_s, lab_s)
    p_b, o_b, l_b = base_step(sharded_b, base_opt_state, tok_s, lab_s)
    assert float(np.asarray(l_z)) == pytest.approx(
        float(np.asarray(l_b)), rel=1e-6)
    for key in ("wqkv", "embed", "head"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(p_z[key])),
            np.asarray(jax.device_get(p_b[key])), rtol=1e-5, atol=1e-6,
            err_msg=f"zero-dp param divergence for {key}")
    # The updated moments keep the dp partitioning (the constraint held
    # through the compiled step).
    assert "dp" in list(o_z[0].mu["wqkv"].sharding.spec)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_packed_sequences_match_dense(strategy):
    # Packed-sequence training end to end: segment ids microbatch with
    # the activations, ride the pipeline ring across pp, shard over sp,
    # and mask attention per-microbatch under either sp strategy.
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64,
                            sp_strategy=strategy)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=2, tp=1)
    params, tokens, labels = _setup(cfg, mesh)
    B, T = tokens.shape
    rng = np.random.RandomState(9)
    # 2-4 contiguous segments per row.
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, T), size=3, replace=False))
        seg[b] = np.searchsorted(cuts, np.arange(T), side="right")
    seg = jnp.asarray(seg)

    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2, packed=True)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    seg_s = jax.device_put(seg, data_sharding)

    loss = float(jax.jit(loss_fn)(sharded, tok_s, lab_s, seg_s))
    expected = float(dense_reference_loss(cfg, params, tokens, labels,
                                          segment_ids=seg))
    assert loss == pytest.approx(expected, rel=1e-4)
    # Masking changes the function: the unpacked loss must differ.
    unpacked = float(dense_reference_loss(cfg, params, tokens, labels))
    assert abs(unpacked - expected) > 1e-4

    grads = jax.jit(jax.grad(loss_fn))(sharded, tok_s, lab_s, seg_s)
    ref_grads = jax.grad(
        lambda p: dense_reference_loss(cfg, p, tokens, labels,
                                       segment_ids=seg))(params)
    for key in ("embed", "wqkv", "wo", "head"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(grads[key])),
            np.asarray(ref_grads[key]), rtol=5e-3, atol=1e-5,
            err_msg=f"packed grad mismatch for {key} ({strategy})")

    # The packed TRAIN step exists end to end (loss + optimizer update).
    optimizer = optax.adam(1e-2)
    opt_state = init_opt_state(optimizer, sharded, mesh)
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=2,
                           packed=True)
    sharded, opt_state, l1 = step(sharded, opt_state, tok_s, lab_s, seg_s)
    assert float(np.asarray(l1)) == pytest.approx(expected, rel=1e-4)


@pytest.mark.parametrize("sizes", [dict(dp=2, pp=2, sp=1, tp=2),
                                   dict(dp=2, pp=1, sp=2, tp=2)])
def test_gqa_rope_matches_dense(sizes):
    # Modern-decoder config: grouped-query attention (2 KV heads shared
    # across 4 query heads, projections tp-sharded at their own widths)
    # + rotary positions (GLOBAL positions on the sp-sharded ranks).
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64,
                            n_kv_heads=2, rope=True)
    mesh = build_parallel_mesh(jax.devices(), **sizes)
    params, tokens, labels = _setup(cfg, mesh)
    assert "wq" in params and "wkv" in params and "pos" not in params
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    loss = float(jax.jit(loss_fn)(sharded, tok_s, lab_s))
    expected = float(dense_reference_loss(cfg, params, tokens, labels))
    assert loss == pytest.approx(expected, rel=1e-4)

    grads = jax.jit(jax.grad(loss_fn))(sharded, tok_s, lab_s)
    ref_grads = jax.grad(
        lambda p: dense_reference_loss(cfg, p, tokens, labels))(params)
    for key in ("embed", "wq", "wkv", "wo", "head"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(grads[key])),
            np.asarray(ref_grads[key]), rtol=5e-3, atol=1e-5,
            err_msg=f"gqa/rope grad mismatch for {key} with {sizes}")


def test_sliding_window_matches_dense():
    # SWA through the sharded stack: the dense oracle gets the same
    # window mask; the sharded loss must match, and must differ from
    # full-causal (the window can't silently no-op).
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64,
                            attention_window=8)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=2, tp=1)
    params, tokens, labels = _setup(cfg, mesh)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    loss = float(jax.jit(loss_fn)(
        sharded, jax.device_put(tokens, data_sharding),
        jax.device_put(labels, data_sharding)))
    expected = float(dense_reference_loss(cfg, params, tokens, labels))
    assert loss == pytest.approx(expected, rel=1e-4)
    import dataclasses
    full = float(dense_reference_loss(
        dataclasses.replace(cfg, attention_window=None), params, tokens,
        labels))
    assert abs(full - expected) > 1e-4


def test_remat_matches_dense():
    # jax.checkpoint must not change the math — only when activations
    # are recomputed. Same oracle check as the non-remat path.
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64, remat=True)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=2, tp=1)
    params, tokens, labels = _setup(cfg, mesh)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    loss = float(jax.jit(loss_fn)(sharded, tok_s, lab_s))
    expected = float(dense_reference_loss(cfg, params, tokens, labels))
    assert loss == pytest.approx(expected, rel=1e-4)

    grads = jax.jit(jax.grad(loss_fn))(sharded, tok_s, lab_s)
    ref_grads = jax.grad(
        lambda p: dense_reference_loss(cfg, p, tokens, labels))(params)
    for key in ("embed", "wqkv", "w1", "head"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(grads[key])),
            np.asarray(ref_grads[key]), rtol=5e-3, atol=1e-5,
            err_msg=f"remat grad mismatch for {key}")


# The MoE decoder is held to the benchmark's plain float32 reference
# (benchmark/reference_moe.py: RMSNorm, QK-norm, RoPE, dropless top-k
# gated SiLU experts, both router loss terms), not to
# dense_reference_loss.
def _moe_cfg(**kw):
    sizes = dict(vocab=64, d_model=32, n_heads=4, d_head=8, n_layers=2,
                 max_seq=64, use_moe=True, n_experts=4, d_expert=64,
                 moe_top_k=2, norm="rmsnorm", qk_norm=True, rope=True,
                 router_aux_loss_coef=0.01, router_z_loss_coef=0.001)
    return TransformerConfig(**dict(sizes, **kw))


def _moe_reference(cfg, params, tokens, labels):
    from benchmark import reference_moe

    (loss, _), grads = reference_moe.decoder_moe_loss_and_grad(
        params, tokens, labels, cfg.moe_top_k, cfg.router_aux_loss_coef,
        cfg.router_z_loss_coef, cfg.norm_eps, cfg.rope_theta)
    return loss, grads


def _moe_loss_and_grads(cfg, mesh, params, tokens, labels):
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=2)
    sharded = shard_params(params, cfg, mesh)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    return jax.jit(jax.value_and_grad(loss_fn))(
        sharded, jax.device_put(tokens, data_sharding),
        jax.device_put(labels, data_sharding))


@pytest.mark.full
def test_moe_grads_match_dense():
    # Validates the differentiable path through routing, sort-by-expert
    # dispatch over the expert-parallel axis, the grouped matmuls and the
    # combine, under dp x pp x tp.
    cfg = _moe_cfg()
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=1, tp=2)
    params, tokens, labels = _setup(cfg, mesh)
    _, grads = _moe_loss_and_grads(cfg, mesh, params, tokens, labels)
    _, ref_grads = _moe_reference(cfg, params, tokens, labels)
    for key in ("router", "wg", "wu", "wd", "gq", "gk", "embed", "head"):
        got = np.asarray(jax.device_get(grads[key]))
        want = np.asarray(ref_grads[key])
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=1e-5,
            err_msg=f"moe grad mismatch for {key}")


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_loss_matches_dense(top_k):
    cfg = _moe_cfg(moe_top_k=top_k)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=1, tp=2)
    params, tokens, labels = _setup(cfg, mesh)
    loss, _ = _moe_loss_and_grads(cfg, mesh, params, tokens, labels)
    expected, _ = _moe_reference(cfg, params, tokens, labels)
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)


def test_train_step_improves_loss():
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=4, max_seq=64)
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=2, tp=1)
    params, tokens, labels = _setup(cfg, mesh)
    optimizer = optax.adam(1e-2)
    sharded = shard_params(params, cfg, mesh)
    opt_state = init_opt_state(optimizer, sharded, mesh)
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=2)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tok_s = jax.device_put(tokens, data_sharding)
    lab_s = jax.device_put(labels, data_sharding)
    losses = []
    p, o = sharded, opt_state
    for _ in range(8):
        p, o, loss = step(p, o, tok_s, lab_s)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_moe_sp2_grads_match_dense():
    # MoE combined with sequence parallelism (ring attention over sp=2):
    # the exact axis combination the driver's dryrun exercises; gradients
    # must still match the reference, whose load-balance term is taken
    # over whole sequences (f and P are averaged over the sp axis).
    cfg = _moe_cfg()
    mesh = build_parallel_mesh(jax.devices(), dp=2, pp=2, sp=2, tp=1)
    params, tokens, labels = _setup(cfg, mesh)
    loss, grads = _moe_loss_and_grads(cfg, mesh, params, tokens, labels)
    expected, ref_grads = _moe_reference(cfg, params, tokens, labels)
    assert float(loss) == pytest.approx(float(expected), rel=1e-5)
    for key in ("router", "wg", "wu", "wd", "embed", "head", "wqkv"):
        got = np.asarray(jax.device_get(grads[key]))
        want = np.asarray(ref_grads[key])
        np.testing.assert_allclose(
            got, want, rtol=5e-3, atol=1e-5,
            err_msg=f"moe+sp grad mismatch for {key}")


def test_dryrun_config_train_step():
    # Twin of __graft_entry__.dryrun_multichip's 8-device branch — the
    # identical factoring, model config, microbatching, and data layout —
    # so the driver is never the first execution of this configuration.
    from horovod_tpu.parallel.mesh import factor_devices

    n = len(jax.devices())
    sizes = factor_devices(n, dp=2, pp=2, sp=2, tp=n // 8)
    mesh = build_parallel_mesh(jax.devices(), **sizes)
    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, d_head=8, n_layers=2 * sizes["pp"],
        max_seq=16 * sizes["sp"], use_moe=True,
        n_experts=2 * sizes["dp"], d_expert=64)
    params = init_params(cfg, jax.random.PRNGKey(0), n_stages=sizes["pp"])
    sharded = shard_params(params, cfg, mesh)
    optimizer = optax.adam(1e-3)
    opt_state = init_opt_state(optimizer, sharded, mesh)
    B, T = 2 * max(2, sizes["dp"]), 8 * sizes["sp"]
    rng = np.random.RandomState(0)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab, (B, T)), jnp.int32),
        data_sharding)
    labels = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab, (B, T)), jnp.int32),
        data_sharding)
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=2)
    p, o = sharded, opt_state
    for _ in range(2):
        p, o, loss = step(p, o, tokens, labels)
        assert np.isfinite(float(np.asarray(loss)))


@pytest.fixture
def toy_kind():
    """A seventh kind entered into the table, a linear mixer over one leaf
    under a prefix of its own; the table is as it was afterwards."""
    from horovod_tpu.models import transformer

    refused = "packed documents through a toy layer are not built: it is a toy"
    entry = transformer.Mixer(
        group="toy",
        specs=lambda cfg: {"t_w": P("pp")},
        init=lambda cfg, rng, lead, norm: {"t_w": norm(
            jax.random.fold_in(rng, 99), lead + (cfg.d_model, cfg.d_model),
            cfg.d_model ** -0.5)},
        # Whole on every tp member: its share of what the block sums.
        mixer=lambda cfg, h, lp, seg, gathered_seg: jnp.einsum(
            "btd,de->bte", h, lp["t_w"]) / jax.lax.psum(1, "tp"),
        refuses=lambda cfg: {"packed": refused})
    before = dict(transformer.MIXERS)
    transformer.MIXERS["toy"] = entry
    yield "toy", refused
    transformer.MIXERS.clear()
    transformer.MIXERS.update(before)


def test_a_new_layer_kind_is_one_entry_of_the_table(toy_kind):
    """The seam: with nothing but its entry in ``MIXERS`` a new kind is
    checked, laid out, drawn, sharded, stepped through and refused."""
    kind, refused = toy_kind
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                            d_ff=64, n_layers=2, max_seq=64,
                            layer_types=("attention", kind))
    mesh = build_parallel_mesh(jax.devices()[:2], dp=1, pp=1, sp=1, tp=2)
    params, tokens, labels = _setup(cfg, mesh)
    assert params["t_w"].shape == (1, 1, 32, 32)  # one layer of the kind
    assert params["wqkv"].shape[:2] == (1, 1)
    before = np.asarray(params["t_w"])  # the step takes its arguments
    sharded = shard_params(params, cfg, mesh)
    opt = optax.sgd(0.1)
    step = make_train_step(cfg, opt, mesh, n_microbatches=1)
    new, _, loss = step(sharded, init_opt_state(opt, sharded, mesh), tokens,
                        labels)
    assert np.isfinite(float(loss))
    # Plain SGD: the toy leaf moved by its gradient, which is not zero.
    assert np.abs(np.asarray(new["t_w"]) - before).max() > 0
    with pytest.raises(ValueError, match=refused):
        make_loss_fn(cfg, mesh, n_microbatches=1, packed=True)
    with pytest.raises(ValueError, match="layer_types must name"):
        TransformerConfig(n_layers=1, layer_types=("no such kind",))
