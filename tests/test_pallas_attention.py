"""Pallas flash-attention kernel (ops/pallas_attention.py): interpreter
mode on the CPU mesh validates the same kernel Mosaic compiles on TPU.
Oracle: dense softmax attention in fp32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas_attention import flash_attention


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    """Off the chip the kernels run only where interpretation was asked
    for (``_resolve_dispatch``); every test in this file asks."""
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")


def _dense(q, k, v, causal, q_off=0, k_off=0, window=None, seg=None):
    """The ONE dense oracle: causal/offset/window/segment masks compose
    here exactly as the kernels compose them."""
    D = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    iq = jnp.arange(q.shape[1])[:, None] + q_off
    ik = jnp.arange(k.shape[1])[None, :] + k_off
    if causal:
        s = jnp.where((iq >= ik)[None, None], s, -1e30)
        if window is not None:
            s = jnp.where((iq - ik < window)[None, None], s, -1e30)
    if seg is not None:
        allowed = seg[:, None, :, None] == seg[:, None, None, :]
        s = jnp.where(allowed, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))


def _qkv(B=2, T=32, H=4, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.randn(B, T, H, D), jnp.float32)  # noqa
    return mk(seed), mk(seed + 1), mk(seed + 2)


@pytest.mark.parametrize("causal", [True, False])
def test_matches_dense_oracle(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, use_pallas=True)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multi_tile_sequences():
    # T > block size: the online-softmax carry across k tiles is exercised.
    q, k, v = _qkv(B=1, T=256, H=2, D=8)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [1, 8, 24])
def test_sliding_window_matches_dense(window):
    # Single-tile case (T=256 -> one 256-wide tile): the in-tile mask.
    q, k, v = _qkv(B=1, T=256, H=2, D=8)
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          window=window)
    ref = _dense(q, k, v, True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_tile_culling():
    # T=1536 -> three 512-wide K tiles with window=64 << 512: whole
    # out-of-window K tiles hit the cull predicate (a sign/off-by-one
    # error there drops a LIVE tile and this comparison catches it).
    q, k, v = _qkv(B=1, T=1536, H=1, D=8)
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          window=64)
    ref = _dense(q, k, v, True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_gradients_match_xla_path():
    q, k, v = _qkv(B=1, T=64, H=2, D=8)

    def make(up):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, use_pallas=up, window=16) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for gp, gx in zip(make(True), make(False)):
        assert np.abs(np.asarray(gp)).max() > 0
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                   rtol=2e-4, atol=2e-5)


def test_sliding_window_composes_with_segments():
    q, k, v = _qkv()
    seg = jnp.asarray(np.repeat([[0, 1]], 2, axis=0).repeat(16, axis=1),
                      jnp.int32)  # [2, 32]
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          window=4, q_segment_ids=seg, k_segment_ids=seg)
    # Oracle: window AND segment masks compose.
    ref = _dense(q, k, v, True, window=4, seg=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_window_requires_causal():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_match_dense(causal):
    # The SAME Mosaic kernels, with the ids streamed as extra tiles.
    q, k, v = _qkv()
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 2, axis=0
                                ).repeat(8, axis=1), jnp.int32)  # [2, 32]
    out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                          q_segment_ids=seg, k_segment_ids=seg)
    ref = _dense(q, k, v, causal, seg=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_segment_ids_gradients_match_xla_path():
    # Kernel backward (interpret) vs the XLA twin: independent
    # implementations of the same masked flash backward.
    q, k, v = _qkv(B=1, T=64, H=2, D=8)
    seg = jnp.asarray(np.repeat([[0, 1]], 1, axis=0).repeat(32, axis=1),
                      jnp.int32)  # [1, 64]

    def make(up):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, use_pallas=up,
                q_segment_ids=seg, k_segment_ids=seg) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_pallas = make(True)
    g_xla = make(False)
    for gp, gx in zip(g_pallas, g_xla):
        assert np.abs(np.asarray(gp)).max() > 0
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                   rtol=2e-4, atol=2e-5)


def test_block_offsets_ring_use():
    # Ring attention passes rotating block origins: q block at global 16,
    # k block at 0 (fully visible) and at 16 (causal within the block).
    q, k, v = _qkv()
    out = flash_attention(q[:, 16:], k[:, :16], v[:, :16], causal=True,
                          q_off=16, k_off=0, use_pallas=True)
    ref = _dense(q[:, 16:], k[:, :16], v[:, :16], False)  # all visible
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_match_dense():
    q, k, v = _qkv(T=16)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, use_pallas=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_gradients_multi_tile(monkeypatch):
    # T spans several q/k tiles: the backward kernels' VMEM accumulation
    # across the sequential grid dimension is exercised (dq over k tiles,
    # dk/dv over q tiles). Tile caps are shrunk so T=256 genuinely yields
    # a 4x4 tile grid — at the default 512 cap a 256-token sequence is a
    # single tile and the accumulation logic would be dead in this test.
    from horovod_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "BLOCK_Q", 64)
    monkeypatch.setattr(pa, "BLOCK_K", 64)
    q, k, v = _qkv(B=1, T=256, H=2, D=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, use_pallas=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_gradients_bf16():
    q, k, v = _qkv(T=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, use_pallas=True).astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    assert all(g.dtype == jnp.bfloat16 for g in gf)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, True) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=1e-1, atol=1e-1)


def test_untileable_sizes_fall_back():
    # T=20 has no MXU-friendly divisor: the XLA path serves it, same math.
    q, k, v = _qkv(T=20)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    q, k, v = _qkv(T=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, use_pallas=True)
    assert out.dtype == jnp.bfloat16
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_block_state_merge_equals_full():
    # Two K blocks merged with the online-softmax combine must equal full
    # attention — the exact contract ring attention relies on per step.
    from horovod_tpu.ops.pallas_attention import flash_attention_block

    q, k, v = _qkv(T=32)
    acc0, m0, l0 = flash_attention_block(q, k[:, :16], v[:, :16],
                                         q_off=0, k_off=0, causal=True,
                                         use_pallas=True)
    acc1, m1, l1 = flash_attention_block(q, k[:, 16:], v[:, 16:],
                                         q_off=0, k_off=16, causal=True,
                                         use_pallas=True)
    m = np.maximum(m0, m1)
    alive0 = m0 > -1e29
    c0 = np.where(alive0, np.exp(m0 - m), 0.0)
    c1 = np.where(m1 > -1e29, np.exp(m1 - m), 0.0)
    l = l0 * c0 + l1 * c1
    o = (np.asarray(acc0) * np.transpose(c0, (0, 2, 1))[..., None] +
         np.asarray(acc1) * np.transpose(c1, (0, 2, 1))[..., None])
    out = o / np.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_uses_block_kernel():
    # sp>1 ring attention on a 4-device sp mesh must agree with dense
    # attention with the pallas block path enabled via interpret mode.
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ring_attention import ring_attention

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(4), ("sp",))
    B, T, H, D = 2, 32, 2, 8
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False))
    out = fn(q, k, v)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_segments_block_kernel():
    # Packed-sequence ring on the Pallas block path (interpret): the
    # segment ids rotate with the K/V blocks and stream into the
    # segment-tiled kernels; forward AND grads vs the dense masked
    # oracle.
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ring_attention import ring_attention

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(4), ("sp",))
    B, T, H, D = 1, 32, 2, 8
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    seg = jnp.asarray(np.repeat([[0, 1, 2]], B, axis=0
                                ).repeat([10, 12, 10], axis=1), jnp.int32)

    fn = jax.jit(jax.shard_map(
        lambda q, k, v, s: ring_attention(q, k, v, axis_name="sp",
                                          segment_ids=s),
        mesh=mesh, in_specs=(P(None, "sp"),) * 4,
        out_specs=P(None, "sp"), check_vma=False))
    out = fn(q, k, v, seg)
    ref = _dense(q, k, v, True, seg=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, seg).astype(jnp.float32) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(_dense(q, k, v, True, seg=seg) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients():
    # Training through sp>1 ring attention: the backward ring pass (flash
    # backward kernels + rotating dK/dV accumulators) must reproduce the
    # dense-attention gradients.
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.ring_attention import ring_attention

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(4), ("sp",))
    B, T, H, D = 1, 32, 2, 8
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v, True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
