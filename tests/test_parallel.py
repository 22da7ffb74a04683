"""Tests for parallelism primitives: ring attention, MoE routing, SPMD
pipeline, mesh factoring."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.mesh import build_parallel_mesh, factor_devices
from horovod_tpu.parallel.moe import init_moe_params, moe_layer
from horovod_tpu.parallel.pipeline import spmd_pipeline
from horovod_tpu.parallel.ring_attention import (
    local_flash_attention, ring_attention)
from horovod_tpu.parallel.ulysses import (
    context_parallel_attention, ulysses_attention)


def _reference_attention(q, k, v, causal=True, seg=None):
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    B, T, H, D = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    if seg is not None:
        seg = np.asarray(seg)
        allowed = seg[:, None, :, None] == seg[:, None, None, :]
        s = np.where(allowed, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


class TestMeshFactoring:
    def test_default_8(self):
        sizes = factor_devices(8)
        assert sizes["tp"] == 2 and sizes["pp"] == 2 and sizes["sp"] == 2
        assert sizes["dp"] == 1
        assert np.prod(list(sizes.values())) == 8

    def test_explicit(self):
        sizes = factor_devices(8, tp=2, pp=2, sp=1, dp=2)
        assert sizes == {"tp": 2, "pp": 2, "sp": 1, "dp": 2}

    def test_bad_divisor(self):
        with pytest.raises(ValueError):
            factor_devices(8, tp=3)

    def test_build(self):
        mesh = build_parallel_mesh(jax.devices(), tp=2, pp=2, sp=1, dp=2)
        assert mesh.axis_names == ("dp", "pp", "sp", "tp")
        assert mesh.devices.shape == (2, 2, 1, 2)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        B, T, H, D = 2, 16, 2, 8
        sp = 4
        rng = np.random.RandomState(0)
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, H, D).astype(np.float32)
        v = rng.randn(B, T, H, D).astype(np.float32)

        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        shard = NamedSharding(mesh, P(None, "sp"))
        qs, ks, vs = (jax.device_put(t, shard) for t in (q, k, v))
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))
        out = np.asarray(fn(qs, ks, vs))
        expected = _reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_local_flash_matches_reference(self):
        B, T, H, D = 1, 12, 2, 4
        rng = np.random.RandomState(1)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32) for _ in range(3))
        out = np.asarray(local_flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
        expected = _reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_grad_flows(self):
        B, T, H, D = 1, 8, 1, 4
        sp = 2
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                   for _ in range(3))

        def loss(q, k, v):
            out = jax.shard_map(
                lambda q, k, v: ring_attention(q, k, v, "sp"),
                mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
                check_vma=False)(q, k, v)
            return jnp.sum(out ** 2)

        g = jax.jit(jax.grad(loss))(q, k, v)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).max() > 0


class TestUlyssesAttention:
    def _sharded_fn(self, attn_fn, sp, **kw):
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        return jax.jit(jax.shard_map(
            lambda q, k, v: attn_fn(q, k, v, "sp", **kw),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("sp", [2, 4])
    def test_matches_reference(self, causal, sp):
        B, T, H, D = 2, 16, 4, 8
        rng = np.random.RandomState(0)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                   for _ in range(3))
        fn = self._sharded_fn(ulysses_attention, sp, causal=causal)
        out = np.asarray(fn(q, k, v))
        expected = _reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_grads_match_ring(self):
        # Both strategies compute the same function; their autodiff
        # gradients must agree (ulysses: all_to_all transpose; ring:
        # custom VJP second rotation).
        B, T, H, D = 1, 8, 2, 4
        sp = 2
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                   for _ in range(3))

        def make_loss(attn_fn):
            fn = self._sharded_fn(attn_fn, sp)

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        g_u = make_loss(ulysses_attention)(q, k, v)
        g_r = make_loss(ring_attention)(q, k, v)
        for gu, gr in zip(g_u, g_r):
            np.testing.assert_allclose(np.asarray(gu), np.asarray(gr),
                                       rtol=2e-4, atol=2e-5)

    def test_indivisible_heads_rejected(self):
        B, T, H, D = 1, 8, 3, 4
        rng = np.random.RandomState(4)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                   for _ in range(3))
        with pytest.raises(ValueError, match="divisible"):
            self._sharded_fn(ulysses_attention, 2)(q, k, v)

    def test_auto_dispatch(self):
        # H=3 over sp=2 can't use ulysses; auto must fall back to ring.
        # H=4 takes the ulysses path. Both strategies compute the same
        # function, so matching the oracle alone can't tell which path
        # ran — assert the path through the lowered collectives too
        # (ulysses lowers to all-to-all, ring to collective-permute).
        B, T, D = 2, 16, 8
        rng = np.random.RandomState(5)
        for H, want_ulysses in ((3, False), (4, True)):
            q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                       for _ in range(3))
            fn = self._sharded_fn(context_parallel_attention, 2,
                                  strategy="auto")
            txt = fn.lower(q, k, v).as_text().lower().replace("-", "_")
            assert ("all_to_all" in txt) == want_ulysses, \
                f"H={H}: wrong strategy path"
            assert ("collective_permute" in txt) == (not want_ulysses), \
                f"H={H}: wrong strategy path"
            out = np.asarray(fn(q, k, v))
            expected = _reference_attention(q, k, v, causal=True)
            np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_unknown_strategy_rejected(self):
        B, T, H, D = 1, 8, 2, 4
        rng = np.random.RandomState(6)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                   for _ in range(3))
        with pytest.raises(ValueError, match="strategy"):
            self._sharded_fn(context_parallel_attention, 2,
                             strategy="spiral")(q, k, v)


def _run_moe_layer(x, params, ep=1, **kw):
    """moe_layer on an ``ep``-member dp axis: x [B, T, d] sharded over its
    sequences, the experts sharded over the members. Returns (y, stats)."""
    mesh = Mesh(np.array(jax.devices()[:ep]), ("dp",))
    param_specs = {"router": P(), "wg": P("dp"), "wu": P("dp"),
                   "wd": P("dp")}
    sharded = {k: jax.device_put(v, NamedSharding(mesh, param_specs[k]))
               for k, v in params.items()}
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))

    def layer(x, p):
        y, stats = moe_layer(x, p, p["router"].shape[-1], axis_name="dp",
                             **kw)
        return y, {"lb": jax.lax.pmean(stats["lb"], "dp"),
                   "z": jax.lax.pmean(stats["z"], "dp"),
                   "load": jax.lax.psum(stats["load"], "dp"),
                   "windows": jax.lax.pmax(stats["windows"], "dp")}

    fn = jax.jit(jax.shard_map(
        layer, mesh=mesh, in_specs=(P("dp"), param_specs),
        out_specs=(P("dp"), P()), check_vma=False))
    return fn(xs, sharded)


class TestMoE:
    def test_single_axis_identity_routing(self):
        # ep axis of size 2, 4 experts (2 local each), one expert a token
        ep = 2
        T, d, f, E = 16, 8, 16, 4
        params = init_moe_params(jax.random.PRNGKey(0), d, f, E)
        x = jax.random.normal(jax.random.PRNGKey(1), (ep, T, d), jnp.float32)
        out, stats = _run_moe_layer(x, params, ep)
        out = np.asarray(out)
        assert out.shape == (ep, T, d)
        assert np.isfinite(out).all()
        # Every token reached its expert: nothing has a capacity.
        assert int(np.asarray(stats["load"]).sum()) == ep * T

        # Oracle: dense computation of top-1 MoE (the k=1 case of the
        # shared top-k oracle).
        expected = _dense_moe_oracle(np.asarray(x).reshape(ep * T, d),
                                     params, top_k=1)
        np.testing.assert_allclose(out.reshape(ep * T, d), expected,
                                   rtol=1e-3, atol=1e-4)


class TestSlidingWindow:
    """Sliding-window (SWA) masking across both context-parallel
    strategies: the window is global-position based, so it crosses the
    ring's rotating block boundaries via the q/k offsets."""

    def _oracle(self, q, k, v, window):
        q64, k64, v64 = (np.asarray(t, np.float64) for t in (q, k, v))
        B, T, H, D = q64.shape
        s = np.einsum("bqhd,bkhd->bhqk", q64, k64) / np.sqrt(D)
        iq = np.arange(T)[:, None]
        ik = np.arange(T)[None, :]
        allowed = (iq >= ik) & (iq - ik < window)
        s = np.where(allowed[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", p, v64)

    @pytest.mark.parametrize("attn,sp", [(ring_attention, 4),
                                         (ulysses_attention, 2)])
    def test_matches_reference(self, attn, sp):
        B, T, H, D = 2, 16, 4, 8
        rng = np.random.RandomState(11)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                   for _ in range(3))
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: attn(q, k, v, "sp", window=5),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))
        out = np.asarray(fn(q, k, v))
        np.testing.assert_allclose(out, self._oracle(q, k, v, 5),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_ring_vs_ulysses(self):
        B, T, H, D = 1, 16, 2, 8
        rng = np.random.RandomState(12)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
                   for _ in range(3))
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))

        def grads(attn):
            fn = jax.jit(jax.shard_map(
                lambda q, k, v: attn(q, k, v, "sp", window=6),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp"), check_vma=False))

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        for gr, gu in zip(grads(ring_attention), grads(ulysses_attention)):
            assert np.abs(np.asarray(gr)).max() > 0
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gu),
                                       rtol=2e-4, atol=2e-5)


class TestGQA:
    """Grouped-query attention at the strategy level: K/V enter with
    fewer heads, ride the sp fabric at that width, and the result must
    equal expand-then-attend."""

    @pytest.mark.parametrize("attn,sp", [(ring_attention, 4),
                                         (ulysses_attention, 2)])
    def test_matches_expanded_reference(self, attn, sp):
        B, T, H, Hkv, D = 2, 16, 4, 2, 8
        rng = np.random.RandomState(13)
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, Hkv, D).astype(np.float32)
        v = rng.randn(B, T, Hkv, D).astype(np.float32)
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: attn(q, k, v, "sp"),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))
        out = np.asarray(fn(q, k, v))
        g = H // Hkv
        expected = _reference_attention(q, np.repeat(k, g, axis=2),
                                        np.repeat(v, g, axis=2))
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_auto_falls_back_to_ring_for_indivisible_kv(self):
        # H=4 divides sp=4 but Hkv=2 doesn't: auto must pick ring (the
        # documented fallback), not crash in ulysses' KV split.
        B, T, H, Hkv, D = 1, 16, 4, 2, 8
        rng = np.random.RandomState(15)
        q = rng.randn(B, T, H, D).astype(np.float32)
        k = rng.randn(B, T, Hkv, D).astype(np.float32)
        v = rng.randn(B, T, Hkv, D).astype(np.float32)
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: context_parallel_attention(q, k, v, "sp",
                                                       strategy="auto"),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False))
        txt = fn.lower(q, k, v).as_text().lower().replace("-", "_")
        assert "collective_permute" in txt and "all_to_all" not in txt
        out = np.asarray(fn(q, k, v))
        expected = _reference_attention(q, np.repeat(k, 2, axis=2),
                                        np.repeat(v, 2, axis=2))
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_grads_match_expanded(self):
        # The ring's reduced-width dK/dV accumulation (group-sum) must
        # equal autodiff through explicit expansion.
        B, T, H, Hkv, D = 1, 8, 4, 2, 8
        rng = np.random.RandomState(14)
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))

        def loss_gqa(q, k, v):
            fn = jax.shard_map(
                lambda q, k, v: ring_attention(q, k, v, "sp"),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp"), check_vma=False)
            return jnp.sum(fn(q, k, v) ** 2)

        def loss_expanded(q, k, v):
            fn = jax.shard_map(
                lambda q, k, v: ring_attention(q, k, v, "sp"),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp"), check_vma=False)
            return jnp.sum(fn(q, jnp.repeat(k, 2, axis=2),
                              jnp.repeat(v, 2, axis=2)) ** 2)

        g_gqa = jax.jit(jax.grad(loss_gqa, argnums=(0, 1, 2)))(q, k, v)
        g_exp = jax.jit(jax.grad(loss_expanded, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_gqa, g_exp):
            assert a.shape == b.shape
            assert np.abs(np.asarray(a)).max() > 0
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestSegmentIds:
    """Packed-sequence masking across the attention stack: local flash,
    the ring (ids rotating with K/V), and ulysses (ids all-gathered)."""

    B, T, H, D = 2, 16, 4, 8

    def _data(self, seed=0):
        rng = np.random.RandomState(seed)
        q, k, v = (rng.randn(self.B, self.T, self.H, self.D
                             ).astype(np.float32) for _ in range(3))
        # Contiguous packed segments, different per batch row.
        seg = np.stack([
            np.repeat([0, 1, 2], [5, 6, 5]),
            np.repeat([0, 1], [9, 7]),
        ]).astype(np.int32)
        return q, k, v, seg

    def _sharded(self, attn_fn, sp, **kw):
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        return jax.jit(jax.shard_map(
            lambda q, k, v, s: attn_fn(q, k, v, "sp", segment_ids=s, **kw),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
            out_specs=P(None, "sp"), check_vma=False))

    @pytest.mark.parametrize("causal", [True, False])
    def test_local_flash_matches_reference(self, causal):
        from horovod_tpu.ops.pallas_attention import flash_attention

        q, k, v, seg = self._data()
        out = np.asarray(flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
            q_segment_ids=seg, k_segment_ids=seg))
        expected = _reference_attention(q, k, v, causal=causal, seg=seg)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("sp", [2, 4])
    def test_ring_matches_reference(self, sp):
        q, k, v, seg = self._data()
        out = np.asarray(self._sharded(ring_attention, sp)(q, k, v, seg))
        expected = _reference_attention(q, k, v, causal=True, seg=seg)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_ulysses_matches_reference(self):
        q, k, v, seg = self._data()
        out = np.asarray(self._sharded(ulysses_attention, 2)(q, k, v, seg))
        expected = _reference_attention(q, k, v, causal=True, seg=seg)
        np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-5)

    def test_grads_ring_vs_ulysses(self):
        # Independent backward plans (ring's custom VJP second rotation
        # vs autodiff through ulysses' all_to_alls) must agree — and
        # both must show zero cross-segment leakage.
        q, k, v, seg = self._data(seed=3)
        segj = jnp.asarray(seg)

        def make_grads(attn_fn):
            fn = self._sharded(attn_fn, 2)

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v, segj) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

        g_r = make_grads(ring_attention)
        g_u = make_grads(ulysses_attention)
        for gr, gu in zip(g_r, g_u):
            assert np.abs(np.asarray(gr)).max() > 0
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gu),
                                       rtol=2e-4, atol=2e-5)


def _dense_moe_oracle(x, params, top_k, renormalize=False):
    """Dropless top-k MoE oracle in float64, gated SiLU experts, the
    router's probabilities as they are unless ``renormalize``. Shared by
    the top-1 and top-2 tests so the two stay in sync by construction."""
    x64 = np.asarray(x, np.float64)
    logits = x64 @ np.asarray(params["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :top_k]
    gates = np.take_along_axis(probs, order, axis=-1)
    if renormalize:
        gates = gates / gates.sum(-1, keepdims=True)
    wg, wu, wd = (np.asarray(params[k], np.float64)
                  for k in ("wg", "wu", "wd"))
    out = np.zeros_like(x64)
    for t in range(x64.shape[0]):
        for j in range(top_k):
            e = order[t, j]
            g = x64[t] @ wg[e]
            h = g / (1.0 + np.exp(-g)) * (x64[t] @ wu[e])  # silu(g) * up
            out[t] += gates[t, j] * (h @ wd[e])
    return out


class TestMoETop2:
    @pytest.mark.parametrize("renormalize", [False, True])
    def test_top2_matches_dense(self, renormalize):
        # The gates are the router's probabilities as they are; dividing
        # them by their sum is a field (norm_topk_prob) and a different
        # result: each oracle is matched by its own setting only.
        ep, T, d, f, E = 2, 16, 8, 16, 4
        params = init_moe_params(jax.random.PRNGKey(0), d, f, E)
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                         (ep, T, d), jnp.float32))
        out, _ = _run_moe_layer(jnp.asarray(x), params, ep, top_k=2,
                                norm_topk_prob=renormalize)
        out = np.asarray(out).reshape(ep * T, d)
        flat = x.reshape(ep * T, d)
        expected = _dense_moe_oracle(flat, params, 2, renormalize)
        np.testing.assert_allclose(out, expected, rtol=1e-3, atol=1e-4)
        other = _dense_moe_oracle(flat, params, 2, not renormalize)
        assert np.abs(out - other).max() > 0.05 * np.abs(other).max()

    def test_aux_loss_balance(self):
        # A uniform router (zero weights -> equal probs) must score the
        # load-balance term at top_k exactly; a collapsed router (huge
        # weight onto expert 0) must score ~E.
        ep, T, d, f, E = 2, 32, 8, 16, 4
        params = init_moe_params(jax.random.PRNGKey(0), d, f, E)
        x = jax.random.normal(jax.random.PRNGKey(1), (ep, T, d),
                              jnp.float32)

        params_uni = dict(params, router=jnp.zeros((d, E), jnp.float32))
        _, stats = _run_moe_layer(x, params_uni, ep, top_k=1)
        # Uniform probs: P_e = 1/E exactly; argmax ties resolve to
        # expert 0, so f_0 = 1 and lb = E * (1 * 1/E) = 1.0 = top_k.
        assert float(stats["lb"]) == pytest.approx(1.0, rel=1e-5)
        # logsumexp of E zeros is log E at every token.
        assert float(stats["z"]) == pytest.approx(np.log(E) ** 2, rel=1e-5)

        # Collapse: first router column dominates. The router is linear
        # (no bias), so positive features make logits[:, 0] large for
        # every token.
        g = np.zeros((d, E), np.float32)
        g[:, 0] = 10.0
        x_pos = jnp.abs(x) + 0.5
        _, stats = _run_moe_layer(
            x_pos, dict(params, router=jnp.asarray(g)), ep, top_k=1)
        assert float(stats["lb"]) > 0.9 * E
        # Nothing dropped though every token wants expert 0.
        np.testing.assert_array_equal(np.asarray(stats["load"]),
                                      [ep * T, 0, 0, 0])

    def test_top_k_validated(self):
        ep, T, d, f, E = 2, 8, 8, 16, 4
        params = init_moe_params(jax.random.PRNGKey(0), d, f, E)
        x = jax.random.normal(jax.random.PRNGKey(1), (ep, T, d),
                              jnp.float32)
        with pytest.raises(ValueError, match="top_k"):
            _run_moe_layer(x, params, ep, top_k=0)


class TestPipeline:
    def test_two_stage_scaling(self):
        S, M = 2, 4
        mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
        # stage s multiplies by (s+2): total factor 2*3=6
        stage_scales = jnp.asarray([2.0, 3.0])
        mb = jnp.arange(M * 4, dtype=jnp.float32).reshape(M, 4)

        def stage_fn(scale, x):
            return x * scale

        fn = jax.jit(jax.shard_map(
            lambda scales, mb: spmd_pipeline(
                stage_fn, scales[0], mb, axis_name="pp"),
            mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
            check_vma=False))
        out = np.asarray(fn(stage_scales, mb))
        np.testing.assert_allclose(out, np.asarray(mb) * 6.0)

    def test_four_stage_grad(self):
        S, M = 4, 4
        mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
        scales = jnp.asarray([1.5, 2.0, 0.5, 3.0])
        mb = jnp.ones((M, 4), jnp.float32)

        def loss(scales, mb):
            out = jax.shard_map(
                lambda s, m: spmd_pipeline(
                    lambda p, x: x * p, s[0], m, axis_name="pp"),
                mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
                check_vma=False)(scales, mb)
            return jnp.sum(out)

        val, g = jax.jit(jax.value_and_grad(loss))(scales, mb)
        total = float(np.prod(np.asarray(scales)))
        np.testing.assert_allclose(float(val), M * 4 * total, rtol=1e-5)
        # d/ds_i = M*4*prod/scale_i
        expected_g = M * 4 * total / np.asarray(scales)
        np.testing.assert_allclose(np.asarray(g), expected_g, rtol=1e-5)
