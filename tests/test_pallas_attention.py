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


def _force_plan(monkeypatch, tile_cap=None, heads_cap=None, chunk_cap=None):
    """Lower the constants ``kernel_plan`` works from: a sub-tile forced
    small makes a short sequence walk many tiles."""
    from horovod_tpu.ops import pallas_attention as pa

    for name, cap in (("_TILE_CAP", tile_cap), ("_MAX_HEADS", heads_cap),
                      ("_CHUNK_CAP", chunk_cap)):
        if cap is not None:
            monkeypatch.setattr(pa, name, cap)


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
    # T=1536 with window=64 << the sub-tile: whole out-of-window K
    # sub-tiles lie outside the walk's bounds (a sign/off-by-one error
    # there drops a LIVE tile and this comparison catches it).
    from horovod_tpu.ops import pallas_attention as pa

    plan = pa.kernel_plan(1, 1536, 1536, 8, jnp.float32, True, 64)
    n_q, n_k = 1536 // plan.tile_q, 1536 // plan.tile_k
    causal_tiles = pa.kernel_plan(1, 1536, 1536, 8, jnp.float32,
                                  True).tiles_visited
    assert n_k >= 3 and plan.tiles_visited <= 2 * n_q - 1 < causal_tiles
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


@pytest.mark.parametrize("chunk_cap", [None, 128])
def test_gradients_multi_tile(monkeypatch, chunk_cap):
    # T spans several q/k tiles: the backward kernels' VMEM accumulation
    # along the walk is exercised (dq over k tiles, dk/dv over q tiles),
    # and with a chunk of 128 across the sequential grid dimension too.
    # The sub-tile is forced to 64 so T=256 genuinely yields a 4x4 walk —
    # at the plan's own sub-tile a 256-token sequence is a single tile
    # and the accumulation logic would be dead in this test.
    from horovod_tpu.ops import pallas_attention as pa

    _force_plan(monkeypatch, tile_cap=64, chunk_cap=chunk_cap)
    plan = pa.kernel_plan(2, 256, 256, 8, jnp.float32, True, kind="dkv")
    assert plan.tile_q == plan.tile_k == 64
    assert plan.grid[1:] == ((1, 1) if chunk_cap is None else (2, 2))
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


# ---------------------------------------------------------------------------
# The grid step of PR 27: several heads and a resident chunk a step, the
# walk over sub-tiles inside the kernel. Oracle: the XLA twins
# (``use_pallas=False`` is ``_xla_flash`` and its autodiff).
# ---------------------------------------------------------------------------

_TOL = {jnp.float32: dict(fwd=dict(rtol=2e-5, atol=2e-5),
                          grad=dict(rtol=2e-4, atol=1e-4)),
        jnp.bfloat16: dict(fwd=dict(rtol=2e-2, atol=2e-2),
                           grad=dict(rtol=1e-1, atol=1e-1))}


def _fwd_and_grads(q, k, v, use_pallas, **kw):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, use_pallas=use_pallas,
                              **kw)
        return jnp.sum(out.astype(jnp.float32) ** 2) / out.shape[1], out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


def _assert_matches_xla(q, k, v, dtype, **kw):
    got = _fwd_and_grads(*(x.astype(dtype) for x in (q, k, v)), True, **kw)
    # The oracle takes the float32 inputs for bf16 too, as
    # test_gradients_bf16 does.
    want = _fwd_and_grads(q, k, v, False, **kw)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype, name
        assert np.abs(np.asarray(a, np.float32)).max() > 0, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=name, **_TOL[dtype]["fwd" if name == "out" else "grad"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,D,caps,heads", [
    # gpt2s-t128: the preferred 16 heads a step do not divide B*H = 24.
    (2, 128, 12, 64, {}, 12),
    # ... and 8 heads a step span the batch boundary.
    (2, 128, 12, 64, {"heads_cap": 8}, 8),
    # gpt2s-t1024: two heads a step, the whole sequence resident.
    (1, 1024, 12, 64, {}, 2),
    # olmoe's head width, several sub-tiles a chunk and two chunks a side.
    (1, 1024, 16, 128, {"chunk_cap": 512, "tile_cap": 128}, 8),
])
def test_cell_shapes_match_xla(monkeypatch, dtype, B, T, H, D, caps,
                               heads):
    from horovod_tpu.ops import pallas_attention as pa

    _force_plan(monkeypatch, **caps)
    for kind in ("fwd", "dq", "dkv"):
        plan = pa.kernel_plan(B * H, T, T, D, dtype, True, kind=kind)
        assert plan.heads == heads and plan.grid[0] == B * H // heads
        if "chunk_cap" in caps:
            assert plan.grid[1:] == (2, 2) and plan.chunk_q // plan.tile_q > 1
    q, k, v = _qkv(B=B, T=T, H=H, D=D, seed=11)
    _assert_matches_xla(q, k, v, dtype)


@pytest.mark.parametrize("caps", [{"tile_cap": 64},
                                  {"tile_cap": 64, "chunk_cap": 128}])
def test_segment_ids_multi_tile(monkeypatch, caps):
    # Segment boundaries inside and on sub-tile edges; every visited tile
    # takes the segment mask, interior ones included.
    _force_plan(monkeypatch, **caps)
    q, k, v = _qkv(B=2, T=256, H=2, D=16, seed=5)
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 2, axis=0).repeat(
        [40, 88, 64, 64], axis=1), jnp.int32)
    _assert_matches_xla(q, k, v, jnp.float32, q_segment_ids=seg,
                        k_segment_ids=seg)


@pytest.mark.parametrize("window,caps", [
    (16, {"tile_cap": 64}),                      # narrower than a sub-tile
    (100, {"tile_cap": 64}),                     # straddles sub-tiles
    (200, {"tile_cap": 32, "chunk_cap": 64}),    # wider than a chunk
])
def test_window_multi_tile(monkeypatch, window, caps):
    _force_plan(monkeypatch, **caps)
    q, k, v = _qkv(B=1, T=256, H=2, D=16, seed=7)
    _assert_matches_xla(q, k, v, jnp.float32, window=window)
    ref = _dense(q, k, v, True, window=window)
    out = flash_attention(q, k, v, causal=True, use_pallas=True,
                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("q_off,k_off", [(128, 0), (64, 64), (0, 128),
                                         (96, 32), (32, 96)])
@pytest.mark.parametrize("window", [None, 80])
def test_ring_block_offsets(monkeypatch, q_off, k_off, window):
    # A ring block strictly below, on and above the diagonal, and two
    # that the diagonal cuts off-centre: the offsets enter the walk's
    # bounds. State, forward and the block gradients against the twins.
    from horovod_tpu.ops import pallas_attention as pa

    _force_plan(monkeypatch, tile_cap=32)
    q, k, v = _qkv(B=1, T=128, H=2, D=16, seed=9)
    for a, b in zip(
            pa.flash_attention_block(q, k, v, q_off, k_off, use_pallas=True,
                                     window=window),
            pa.flash_attention_block(q, k, v, q_off, k_off,
                                     use_pallas=False, window=window)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    out = flash_attention(q, k, v, causal=True, q_off=q_off, k_off=k_off,
                          use_pallas=True, window=window)
    ref = flash_attention(q, k, v, causal=True, q_off=q_off, k_off=k_off,
                          use_pallas=False, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    rng = np.random.RandomState(3)
    do = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    lse = jnp.asarray(rng.rand(1, 2, 128) + 3.0, jnp.float32)
    delta = jnp.asarray(rng.randn(1, 2, 128) * 0.1, jnp.float32)
    for name, a, b in zip(
            ("dq", "dk", "dv"),
            pa.flash_attention_block_grads(q, k, v, do, lse, delta, q_off,
                                           k_off, use_pallas=True,
                                           window=window),
            pa.flash_attention_block_grads(q, k, v, do, lse, delta, q_off,
                                           k_off, use_pallas=False,
                                           window=window)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def _live_tiles(Tq, Tk, tq, tk, window):
    """Brute force: the [tq, tk] sub-tiles in which the causal mask and
    the window keep any element."""
    iq = np.arange(Tq)[:, None]
    ik = np.arange(Tk)[None, :]
    keep = iq >= ik
    if window is not None:
        keep &= iq - ik < window
    return int(keep.reshape(Tq // tq, tq, Tk // tk, tk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("kind", ["fwd", "bwd", "dq", "dkv"])
@pytest.mark.parametrize("BH,T,D,window,heads", [
    (768, 128, 64, None, 16),      # gpt2s-t128
    (96, 1024, 64, None, 2),       # gpt2s-t1024
    (32, 4096, 128, None, 1),      # olmoe-t4096
    (32, 4096, 128, 1000, 1),
    (96, 1024, 64, 100, 2),
])
def test_kernel_plan_at_cell_shapes(kind, BH, T, D, window, heads):
    from horovod_tpu.ops import pallas_attention as pa

    plan = pa.kernel_plan(BH, T, T, D, jnp.bfloat16, True, window,
                          kind=kind)
    assert plan.heads == heads
    # The whole sequence is resident at all three cells' shapes.
    assert (plan.chunk_q, plan.chunk_k) == (T, T)
    assert plan.grid == (BH // heads,) + (1,) * (3 if kind == "bwd" else 2)
    assert T % plan.tile_q == 0 and T % plan.tile_k == 0
    # The fused backward within the budget of the passes it replaces.
    assert plan.vmem_bytes <= pa.VMEM_BUDGET
    # Exactly the tiles the mask leaves, with segment ids too.
    assert plan.tiles_visited == _live_tiles(T, T, plan.tile_q, plan.tile_k,
                                             window)
    seg = pa.kernel_plan(BH, T, T, D, jnp.bfloat16, True, window, kind=kind,
                         segments=True)
    assert seg.tiles_visited == plan.tiles_visited


def test_kernel_plan_long_sequence_keeps_chunk_grid(monkeypatch):
    from horovod_tpu.ops import pallas_attention as pa

    for kind in ("fwd", "dq", "dkv"):
        plan = pa.kernel_plan(8, 65536, 65536, 128, jnp.bfloat16, True,
                              kind=kind)
        assert plan.vmem_bytes <= pa.VMEM_BUDGET
        assert plan.chunk_q < 65536 and plan.chunk_q % plan.tile_q == 0
        n = 65536 // plan.chunk_q
        assert plan.grid == (8 // plan.heads, n, n)
    # Non-causal: every tile.
    plan = pa.kernel_plan(4, 512, 1024, 64, jnp.float32, False)
    assert plan.tiles_visited == (512 // plan.tile_q) * (1024 // plan.tile_k)
    # Counted chunk by chunk, the walk is still the mask's.
    _force_plan(monkeypatch, chunk_cap=1024)
    for kind in ("fwd", "dq", "dkv"):
        short = pa.kernel_plan(8, 4096, 4096, 128, jnp.bfloat16, True,
                               kind=kind)
        assert short.grid[1:] == (4, 4)
        assert short.tiles_visited == _live_tiles(4096, 4096, short.tile_q,
                                                  short.tile_k, None)


@pytest.mark.parametrize("kind", ["fwd", "bwd", "dq", "dkv"])
@pytest.mark.parametrize("segments", [False, True])
@pytest.mark.parametrize("BH,T,D,dtype", [
    (8, 2048, 256, jnp.bfloat16),
    (4, 1024, 1024, jnp.float32),
    (2, 8192, 512, jnp.float32),
    (2, 512, 8192, jnp.float32),     # no chunk of this width fits
    (2, 4104, 64, jnp.float32),      # 8 x 513: 8-wide sub-tiles only
])
def test_kernel_plan_holds_its_budget_or_declines(kind, segments, BH, T, D,
                                                  dtype):
    # Wide heads: a plan either counts itself under the budget it states
    # (and ``vmem_limit_bytes`` follows the count), or there is none and
    # the callers take the XLA twins. Never a plan over the budget.
    from horovod_tpu.ops import pallas_attention as pa

    plan = pa.kernel_plan(BH, T, T, D, dtype, True, segments=segments,
                          kind=kind)
    if D == 8192 or (kind == "bwd" and T * D >= 8192 * 512):
        # The second: float32 dK and dV of 8,192 rows of 512 are 96 MiB.
        assert plan is None
    else:
        assert plan.vmem_bytes <= (pa.BWD_VMEM_BUDGET if kind == "bwd"
                                   else pa.VMEM_BUDGET)
        assert T % plan.chunk_q == 0 and plan.chunk_q % plan.tile_q == 0


def test_untileable_and_too_wide_fall_back_to_xla(monkeypatch):
    # No plan (T with no 8-divisor; a budget nothing fits): forward and
    # gradients are the XLA twins', and no kernel is traced.
    from horovod_tpu.ops import pallas_attention as pa

    assert pa.kernel_plan(4, 100, 100, 16, jnp.float32, True) is None
    monkeypatch.setattr(pa, "VMEM_BUDGET", 1 << 16)
    monkeypatch.setattr(
        pa, "_flash_call",
        lambda *a, **kw: pytest.fail("a kernel was traced without a plan"))
    q, k, v = _qkv(B=1, T=256, H=2, D=16, seed=3)
    assert pa.kernel_plan(2, 256, 256, 16, jnp.float32, True) is None
    _assert_matches_xla(q, k, v, jnp.float32)
    for a, b in zip(
            pa.flash_attention_block(q, k, v, 0, 0, use_pallas=True),
            pa.flash_attention_block(q, k, v, 0, 0, use_pallas=False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Grouped K/V: the K side at its own head count.
# ---------------------------------------------------------------------------

# layout -> (T, the constants forced, what the three plans must then be as
# a function of the group: heads a step, K/V heads a step, passes).
_GROUP_LAYOUTS = {
    # Short T, four heads a step: whole groups (g <= 4) or a part of one.
    "several-heads": (32, dict(heads_cap=4),
                      lambda g: (4, max(1, 4 // g), max(1, g // 4))),
    "one-head": (64, dict(heads_cap=1, tile_cap=32), lambda g: (1, 1, g)),
    # Two chunks a side, two heads a step.
    "chunks": (128, dict(heads_cap=2, chunk_cap=64, tile_cap=32),
               lambda g: (2, max(1, 2 // g), max(1, g // 2))),
}


def _grouped_operands(T, g, mask, seed):
    B, H, D = 2, 8, 16
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, T, h, D), jnp.float32)  # noqa
    q, k, v, do = mk(H), mk(H // g), mk(H // g), mk(H)
    kw = {}
    if mask == "window":
        kw["window"] = T // 2 - 3
    if mask == "segments":
        # Three documents; the boundaries fall inside sub-tiles.
        seg = jnp.asarray(np.tile(np.searchsorted(
            [int(0.3 * T), int(0.7 * T)], np.arange(T), side="right"),
            (B, 1)), jnp.int32)
        kw.update(q_segment_ids=seg, k_segment_ids=seg)
    return q, k, v, do, kw


@pytest.mark.parametrize("mask", ["causal", "window", "segments"])
@pytest.mark.parametrize("layout", list(_GROUP_LAYOUTS))
@pytest.mark.parametrize("g", [1, 2, 4, 8])
def test_grouped_kv_matches_expand_then_attend(monkeypatch, g, layout, mask):
    """The three entry points with K and V at H / g heads, interpreted,
    against the XLA twin on K and V repeated to H heads: output, lse and
    the three gradients, dK and dV summed over each group."""
    from horovod_tpu.ops import pallas_attention as pa

    T, caps, want = _GROUP_LAYOUTS[layout]
    _force_plan(monkeypatch, **caps)
    q, k, v, do, kw = _grouped_operands(T, g, mask, seed=17 + g)
    B, _, H, D = q.shape
    window = kw.get("window")
    for kind in ("fwd", "dq", "dkv"):
        plan = pa.kernel_plan(B * H, T, T, D, q.dtype, True, window,
                              segments=mask == "segments", kind=kind,
                              group=g)
        assert (plan.heads, plan.kv_heads, plan.passes) == want(g), kind
        n_c = T // plan.chunk_q
        assert plan.grid == (
            (B * H // g // plan.kv_heads, n_c, plan.passes * n_c)
            if kind == "dkv" else (B * H // plan.heads, n_c, n_c)), kind
    # The backward that runs is the fused one: the dK/dV pass's step, the
    # group's passes and the chunks each a dimension of their own.
    fused = pa.kernel_plan(B * H, T, T, D, q.dtype, True, window,
                           segments=mask == "segments", kind="bwd", group=g)
    assert (fused.heads, fused.kv_heads, fused.passes) == want(g)
    assert fused.grid == (B * H // g // fused.kv_heads, fused.passes,
                          T // fused.chunk_q, T // fused.chunk_k)
    expand = lambda x: jnp.repeat(x, g, axis=2)                     # noqa
    fold = lambda x: x.reshape(B, T, H // g, g, D).sum(3)           # noqa
    tol, gtol = _TOL[jnp.float32]["fwd"], _TOL[jnp.float32]["grad"]

    def close(a, b, name, **t):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=name, **t)

    # flash_attention: output and jax.grad; the twin differentiates
    # through the repeat, whose transpose is the group sum.
    got = _fwd_and_grads(q, k, v, True, **kw)
    ref = _fwd_and_grads(q, expand(k), expand(v), False, **kw)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got,
                          (*ref[:2], fold(ref[2]), fold(ref[3]))):
        close(a, b, name, **(tol if name == "out" else gtol))
    assert got[2].shape == k.shape and got[3].shape == v.shape

    # flash_attention_block: the block's state, and lse from it.
    state = pa.flash_attention_block(q, k, v, T // 4, 0, use_pallas=True,
                                     **kw)
    ref_state = pa.flash_attention_block(q, expand(k), expand(v), T // 4, 0,
                                         use_pallas=False, **kw)
    for name, a, b in zip(("acc", "m", "l"), state, ref_state):
        close(a, b, name, **tol)
    lse = pa.row_lse(*ref_state[1:])
    close(pa.row_lse(*state[1:]), lse, "lse", **tol)

    # flash_attention_block_grads on that block, with its own lse.
    delta = jnp.sum(do * ref_state[0] / jnp.maximum(
        ref_state[2], 1e-30).transpose(0, 2, 1)[..., None],
        axis=-1).transpose(0, 2, 1)
    grads = pa.flash_attention_block_grads(
        q, k, v, do, lse, delta, T // 4, 0, use_pallas=True, **kw)
    ref_grads = pa.flash_attention_block_grads(
        q, expand(k), expand(v), do, lse, delta, T // 4, 0,
        use_pallas=False, **kw)
    for name, a, b in zip(("dq", "dk", "dv"), grads,
                          (ref_grads[0], fold(ref_grads[1]),
                           fold(ref_grads[2]))):
        close(a, b, "block " + name, **gtol)


def test_grouped_kv_twins_and_errors():
    # Off the kernels the same operands go through the XLA twins, which
    # repeat inside themselves; a head count that does not divide raises.
    q, k, v, do, kw = _grouped_operands(32, 4, "segments", seed=2)
    expand = lambda x: jnp.repeat(x, 4, axis=2)                     # noqa
    got = _fwd_and_grads(q, k, v, False, **kw)
    ref = _fwd_and_grads(q, expand(k), expand(v), False, **kw)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=1e-6, atol=1e-6)
    assert got[2].shape == k.shape
    np.testing.assert_allclose(
        np.asarray(got[2]),
        np.asarray(ref[2].reshape(2, 32, 2, 4, 16).sum(3)),
        rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="query heads over"):
        flash_attention(q, k[:, :, :1].repeat(3, axis=2),
                        v[:, :, :1].repeat(3, axis=2), use_pallas=False)
    with pytest.raises(ValueError, match="query heads over"):
        flash_attention(q, k, v[:, :, :1], use_pallas=False)


# ``kernel_plan`` before the K side had a head count of its own, at every
# shape of docs/diagnostics.md's table (bf16, causal): (heads, chunk_q,
# chunk_k, tile_q, tile_k, unroll, grid, tiles_visited) and the VMEM
# counted for fwd, dq and dkv. A plan is a pure function of the shape.
_PLANS_WITHOUT_A_GROUP = {
    (768, 128, 64, None): ((16, 128, 128, 128, 128, 4, (48, 1, 1), 1),
                           (11010048, 12058624, 14155776)),
    (96, 1024, 64, None): ((2, 1024, 1024, 512, 512, 2, (48, 1, 1), 3),
                           (22020096, 23068672, 25165824)),
    (32, 4096, 128, None): ((1, 4096, 4096, 512, 512, 1, (32, 1, 1), 36),
                            (25165824, 27262976, 31457280)),
    (64, 8192, 128, None): ((1, 4096, 4096, 512, 512, 1, (64, 2, 2), 136),
                            (25165824, 27262976, 31457280)),
    (64, 8192, 128, 2048): ((1, 4096, 4096, 512, 512, 1, (64, 2, 2), 70),
                            (25165824, 27262976, 31457280)),
    (32, 8192, 64, None): ((1, 4096, 4096, 512, 512, 1, (32, 2, 2), 136),
                           (25165824, 27262976, 31457280)),
}


@pytest.mark.parametrize("shape", list(_PLANS_WITHOUT_A_GROUP),
                         ids=lambda s: "-".join(map(str, s)))
def test_kernel_plan_without_a_group_is_unchanged(shape):
    from horovod_tpu.ops import pallas_attention as pa

    BH, T, D, window = shape
    fields, vmem = _PLANS_WITHOUT_A_GROUP[shape]
    for kind, counted in zip(("fwd", "dq", "dkv"), vmem):
        plan = pa.kernel_plan(BH, T, T, D, jnp.bfloat16, True, window,
                              kind=kind)
        assert tuple(plan) == fields + (counted, 1), kind
        assert (plan.kv_heads, plan.shared, plan.passes) == (
            plan.heads, 1, 1)


@pytest.mark.parametrize("BH,D,g,window", [(64, 128, 8, None),
                                           (64, 128, 8, 2048),
                                           (32, 64, 4, None)])
def test_kernel_plan_at_the_grouped_cell_shapes(BH, D, g, window):
    # trinity-mini-t8192 (32 heads over 4, B 2) and granite-h-t8192 (32
    # over 8, B 1) at T 8192: the multi-head plan's step, one head and a
    # chunk of 4096 a side; the forward's and dQ's grid is the Q side's,
    # the dK/dV pass's is over K/V heads with the group's heads in turn
    # on the sequential dimension.
    from horovod_tpu.ops import pallas_attention as pa

    for kind in ("fwd", "dq", "dkv"):
        one = pa.kernel_plan(BH, 8192, 8192, D, jnp.bfloat16, True, window,
                             kind=kind)
        plan = pa.kernel_plan(BH, 8192, 8192, D, jnp.bfloat16, True, window,
                              kind=kind, group=g)
        assert plan == one._replace(
            group=g,
            grid=(BH // g, 2, 2 * g) if kind == "dkv" else (BH, 2, 2))
        assert (plan.heads, plan.kv_heads, plan.passes) == (1, 1, g)
    # The fused backward holds a K/V head's 8,192 rows of dK and dV, so
    # the whole sequence is one chunk; the group's heads take turns.
    fused = pa.kernel_plan(BH, 8192, 8192, D, jnp.bfloat16, True, window,
                           kind="bwd", group=g)
    assert fused.grid == (BH // g, g, 1, 1) and fused.chunk_q == 8192
    assert (fused.heads, fused.tile_q, fused.tiles_visited) == (
        1, 512, plan.tiles_visited)
    assert fused.vmem_bytes == 65011712 <= pa.BWD_VMEM_BUDGET


def test_grouped_calls_are_counted_beside_the_traced():
    from horovod_tpu.common import metrics
    from horovod_tpu.ops import pallas_attention as pa  # noqa: F401

    def counted(g):
        metrics.reset()
        q, k, v, _, _ = _grouped_operands(32, g, "causal", seed=1)
        _fwd_and_grads(q, k, v, True)
        return metrics.counters()

    traced = {f"kernels.traced.flash_{kind}": 1 for kind in ("fwd", "bwd")}
    assert counted(1) == traced
    assert counted(4) == dict(traced, **{
        f"kernels.grouped.flash_{kind}": 1 for kind in ("fwd", "bwd")})


# ---------------------------------------------------------------------------
# The fused backward: dQ, dK and dV from one walk.
# ---------------------------------------------------------------------------


# layout -> (q_off, k_off, out_dtype, chunked): what the library's three
# callers ask of the backward.
_BWD_LAYOUTS = {"one-chunk": (0, 0, None, False),
                "chunked": (0, 0, None, True),
                "ring-block": (128, 64, jnp.float32, True)}


@pytest.mark.parametrize("segments", [False, True], ids=["", "segments"])
@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("layout", list(_BWD_LAYOUTS))
@pytest.mark.parametrize("g", [1, 4, 8])
def test_fused_backward_matches_the_two_passes_and_xla(monkeypatch, g, layout,
                                                       window, segments):
    """``flash_bwd`` against ``flash_dq`` + ``flash_dkv`` and against the
    XLA twin on the same merged operands, lse and delta: one resident
    chunk, chunks forced by a small budget (the group's heads and the Q
    chunks on the sequential dimensions), and a ring block (offsets,
    float32 gradients)."""
    from horovod_tpu.common import metrics
    from horovod_tpu.ops import pallas_attention as pa

    q_off, k_off, out_dtype, chunked = _BWD_LAYOUTS[layout]
    # Two heads a step share a K/V head where there is a group; with one,
    # a budget a byte short of the whole sequence's halves the chunk.
    heads = 1 if chunked else 2
    _force_plan(monkeypatch, tile_cap=32, heads_cap=heads)
    B, T, H, D = 1, 256, 8, 16
    rng = np.random.RandomState(5 + g)
    mk = lambda h: jnp.asarray(rng.randn(B * h, T, D), jnp.float32)  # noqa
    q, k, v, do = mk(H), mk(H // g), mk(H // g), mk(H)
    offs = jnp.asarray([q_off, k_off], jnp.int32)
    q_seg = k_seg = None
    if segments:
        ids = np.searchsorted([int(0.3 * T), int(0.7 * T)], np.arange(T),
                              side="right")
        q_seg = jnp.asarray(np.tile(ids, (B * H, 1)), jnp.int32)
        k_seg = jnp.asarray(np.tile(ids, (B * H // g, 1)), jnp.int32)
    kw = dict(q_seg=q_seg, k_seg=k_seg, window=window)

    def plan_of(kind):
        return pa.kernel_plan(B * H, T, T, D, q.dtype, True, window,
                              segments=segments, kind=kind, group=g,
                              out_dtype=out_dtype)

    if chunked:
        monkeypatch.setattr(pa, "BWD_VMEM_BUDGET",
                            plan_of("bwd").vmem_bytes - 1)
        monkeypatch.setattr(pa, "VMEM_BUDGET", plan_of("dq").vmem_bytes - 1)
    plan = plan_of("bwd")
    n_c = 2 if chunked else 1
    assert plan.grid == (B * H // g // plan.kv_heads, g // plan.shared,
                         n_c, n_c)
    assert (plan.heads, plan.chunk_q, plan.tile_q) == (heads, T // n_c, 32)
    assert plan.vmem_bytes <= pa.BWD_VMEM_BUDGET
    assert plan_of("dkv").chunk_q == T // n_c

    o, lse = pa._flash_forward(q, k, v, offs, True, True, "train", **kw)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    args = (q, k, v, do, lse, delta, offs, True)
    metrics.reset()
    fused = pa._pallas_bwd(*args, True, out_dtype=out_dtype, **kw)
    assert metrics.counters() == dict(
        {"kernels.traced.flash_bwd": 1},
        **({"kernels.grouped.flash_bwd": 1} if g > 1 else {}))
    with monkeypatch.context() as m:
        # A budget of nothing leaves ``_pallas_bwd`` the two passes.
        m.setattr(pa, "BWD_VMEM_BUDGET", 0)
        metrics.reset()
        two = pa._pallas_bwd(*args, True, out_dtype=out_dtype, **kw)
        assert sorted(n for n in metrics.counters() if "traced" in n) == [
            "kernels.traced.flash_dkv", "kernels.traced.flash_dq"]
    twin = pa._xla_block_grads(*args, out_dtype=out_dtype, **kw)
    for name, a, b, c in zip(("dq", "dk", "dv"), fused, two, twin):
        assert a.shape == b.shape == c.shape and a.dtype == b.dtype, name
        assert np.abs(np.asarray(a)).max() > 0, name
        # The same products added in the same order.
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   err_msg=name, rtol=2e-4, atol=2e-5)


def test_fused_backward_declines_by_shape(monkeypatch):
    """No fused plan where a K/V head's dK and dV do not fit: the two
    passes run, by ``kernel_plan``'s word alone."""
    from horovod_tpu.common import metrics
    from horovod_tpu.ops import pallas_attention as pa

    # The roadmap's longest sequences (R4, R5): a head's dK and dV alone
    # are over the budget; the two passes have plans. T 32,768 at D 128
    # is the longest the fused kernel takes, at chunks of 2,048.
    long = pa.kernel_plan(8, 32768, 32768, 128, jnp.bfloat16, True,
                          kind="bwd")
    assert long.grid == (8, 1, 16, 16)
    assert long.vmem_bytes == pa.BWD_VMEM_BUDGET == 80 << 20
    for T, D in ((65536, 128), (131072, 128), (32768, 256)):
        assert pa.kernel_plan(8, T, T, D, jnp.bfloat16, True,
                              kind="bwd") is None
        for kind in ("dq", "dkv"):
            assert pa.kernel_plan(8, T, T, D, jnp.bfloat16, True,
                                  kind=kind) is not None
    # The same at a size the interpreter runs: T 512 at a budget that
    # holds the two passes' chunk of 128 and not the head's 512 rows.
    _force_plan(monkeypatch, tile_cap=32, heads_cap=1)
    q, k, v = _qkv(B=1, T=512, H=2, D=16, seed=9)
    one = pa.kernel_plan(2, 128, 128, 16, jnp.float32, True, kind="bwd")
    monkeypatch.setattr(pa, "BWD_VMEM_BUDGET", one.vmem_bytes)
    monkeypatch.setattr(pa, "VMEM_BUDGET", one.vmem_bytes)
    assert pa.kernel_plan(2, 512, 512, 16, jnp.float32, True,
                          kind="bwd") is None
    assert pa.kernel_plan(2, 512, 512, 16, jnp.float32, True,
                          kind="dkv").grid == (2, 4, 4)
    metrics.reset()
    _assert_matches_xla(q, k, v, jnp.float32)
    assert metrics.counters() == {f"kernels.traced.flash_{kind}": 1
                                  for kind in ("fwd", "dq", "dkv")}
