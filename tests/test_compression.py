"""On-wire gradient compression: structural, numeric, and convergence
proof on the 8-virtual-device CPU mesh.

Four contracts (ISSUE 2 acceptance criteria):

- **Wire dtype is structural**: with ``compression="fp16"`` the compiled
  train-step HLO contains an all-reduce whose operand element type is
  f16 (≈2x fewer wire bytes than the fp32 wire) while parameters and
  optimizer state stay fp32; ZeRO's compiled reduce-scatter likewise.
- **Unset = byte-identical**: with ``HOROVOD_COMPRESSION`` unset, the
  compiled program is identical to the uncompressed path — compression
  cannot change programs under users' feet.
- **Numerics**: compressed vs uncompressed training stays within
  quantization tolerance.
- **Error feedback**: a gradient flow whose per-step gradients round to
  zero in fp16 stalls bitwise under plain fp16 compression and
  converges under ef16 (residuals re-inject the rounding error).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import flax.linen as nn

from hlo_text import collective_results, find_psums, strip_debug_info
from horovod_tpu.common.compression import (
    Compression, ErrorFeedbackCompressor, resolve_compression)
from horovod_tpu.training import (
    init_train_state, make_train_step, replicate_state, shard_batch)


class MLP3(nn.Module):
    feats: tuple = (32, 32, 10)

    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        for f in self.feats:
            x = nn.Dense(f)(x)
            if f != self.feats[-1]:
                x = jax.nn.relu(x)
        return x


def _problem(hvd, compression, donate=False):
    mesh = hvd.mesh()
    model = MLP3()
    opt = optax.sgd(0.1)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 16), jnp.float32)
    state = replicate_state(
        init_train_state(model, opt, rng, sample, compression=compression),
        mesh)
    imgs = jnp.asarray(
        np.random.RandomState(0).rand(16, 16).astype(np.float32))
    lbls = jnp.asarray(
        np.random.RandomState(1).randint(0, 10, 16).astype(np.int32))
    imgs, lbls = shard_batch((imgs, lbls), mesh)
    step = make_train_step(model, opt, mesh, compression=compression,
                           donate=donate)
    return step, state, imgs, lbls


def _allreduce_ops(hlo_text):
    """(element_type, line) per array reduced by an all-reduce in
    compiled HLO text (tests/hlo_text.py)."""
    return [(t, line) for t, _, line in collective_results(hlo_text)]


def _grad_psum_dtypes(step, state, imgs, lbls):
    """Input dtypes of the non-scalar (gradient) psums in the step."""
    jaxpr = jax.make_jaxpr(step)(state, imgs, lbls)
    acc = find_psums(jaxpr.jaxpr)
    return [str(b.eqns[i].invars[0].aval.dtype) for b, i in acc
            if b.eqns[i].invars[0].aval.shape != ()]


# ---- structural: the wire dtype shows in the program -----------------------


@pytest.mark.parametrize("mode,wire", [("fp16", "float16"),
                                       ("bf16", "bfloat16"),
                                       ("ef16", "float16")])
def test_compressed_allreduce_element_type(hvd, mode, wire):
    step, state, imgs, lbls = _problem(hvd, mode)
    # Dataflow level: the gradient psum's operand IS the wire dtype.
    dtypes = _grad_psum_dtypes(step, state, imgs, lbls)
    assert wire in dtypes, (mode, dtypes)
    if wire == "float16":
        # Compiled level: the f16 operand survives XLA's optimization
        # pipeline (the on-wire ≈2x). bf16 is checked at the dataflow
        # level only — the CPU backend legalizes bf16 collectives to f32
        # (no native bf16), which a TPU lowering does not.
        hlo = step.lower(state, imgs, lbls).compile().as_text()
        ops = _allreduce_ops(hlo)
        assert any(t == "f16" for t, _ in ops), (
            f"no f16 all-reduce in compiled HLO under compression={mode}; "
            f"operand types: {[t for t, _ in ops]}")
    # Parameters and optimizer state stay fp32 — only the wire narrows.
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        assert leaf.dtype in (jnp.float32, jnp.int32), leaf.dtype


def test_unset_env_keeps_program_byte_identical(hvd, monkeypatch):
    """HOROVOD_COMPRESSION unset -> the "auto" program is the SAME TEXT
    as the explicitly-uncompressed one, and carries no 16-bit wire."""
    monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
    step_auto, state, imgs, lbls = _problem(hvd, "auto")
    step_none, state_n, _, _ = _problem(hvd, None)
    hlo_auto = step_auto.lower(state, imgs, lbls).compile().as_text()
    hlo_none = step_none.lower(state_n, imgs, lbls).compile().as_text()
    # The same program: debug information (source line numbers of the
    # two call sites) is not part of it.
    assert strip_debug_info(hlo_auto) == strip_debug_info(hlo_none)
    assert all(t == "f32" for t, _ in _allreduce_ops(hlo_auto)), \
        _allreduce_ops(hlo_auto)
    # No 16-bit buffer anywhere in the program: the fp32 model's
    # uncompressed step never materializes a wire cast.
    assert "f16[" not in hlo_auto and "bf16[" not in hlo_auto
    # And what is reduced is the gradient leaves where they lie + the
    # scalar loss pmean, whatever tuple all-reduces XLA packs them into —
    # the same count test_fusion_overlap locks for the uncompressed plan.
    leaves = jax.tree_util.tree_leaves(state.params)
    assert len(_allreduce_ops(hlo_auto)) == len(leaves) + 1, \
        _allreduce_ops(hlo_auto)


def test_env_var_engages_compression(hvd, monkeypatch):
    """HOROVOD_COMPRESSION=fp16 flips the "auto" path to the f16 wire
    (the runtime was initialized without it, so this exercises the
    raw-env half of resolve_compression's precedence)."""
    monkeypatch.setenv("HOROVOD_COMPRESSION", "fp16")
    step, state, imgs, lbls = _problem(hvd, "auto")
    hlo = step.lower(state, imgs, lbls).compile().as_text()
    assert any(t == "f16" for t, _ in _allreduce_ops(hlo))


def test_resolve_compression_forms():
    assert resolve_compression(None) is None
    assert resolve_compression("none") is None
    assert resolve_compression(Compression.none) is None
    assert resolve_compression("fp16") is Compression.fp16
    assert resolve_compression(Compression.bf16) is Compression.bf16
    ef = resolve_compression("ef16")
    assert isinstance(ef, ErrorFeedbackCompressor) and ef.error_feedback
    assert str(ef.wire_dtype(jnp.float32)) == "float16"
    assert ef.wire_dtype(jnp.int32) is None
    with pytest.raises(ValueError, match="unknown compression"):
        resolve_compression("fp8")
    with pytest.raises(TypeError, match="framework compressor"):
        resolve_compression(type("Fake", (), {"compress": lambda t: t})())


def test_invalid_env_value_is_ignored(monkeypatch):
    monkeypatch.setenv("HOROVOD_COMPRESSION", "pf16")  # typo
    assert resolve_compression("auto") is None


# ---- numerics ---------------------------------------------------------------


@pytest.mark.parametrize("mode", ["fp16", "bf16", "ef16"])
def test_compressed_numerics_within_tolerance(hvd, mode):
    step_n, state_n, imgs, lbls = _problem(hvd, None)
    step_c, state_c, _, _ = _problem(hvd, mode)
    for _ in range(3):
        state_n, loss_n = step_n(state_n, imgs, lbls)
        state_c, loss_c = step_c(state_c, imgs, lbls)
    assert abs(float(loss_n) - float(loss_c)) < 5e-2
    for pn, pc in zip(jax.tree_util.tree_leaves(state_n.params),
                      jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_allclose(np.asarray(pn), np.asarray(pc),
                                   atol=5e-3, rtol=0)


def test_ef_state_structure(hvd):
    """ef16 adds fp32 residuals to the optimizer state; stateless modes
    leave the state pytree unchanged (residual child is None)."""
    _, state_ef, _, _ = _problem(hvd, "ef16")
    _, state_fp, _, _ = _problem(hvd, "fp16")
    assert state_ef.opt_state.residual is not None
    res_leaves = jax.tree_util.tree_leaves(state_ef.opt_state.residual)
    p_leaves = jax.tree_util.tree_leaves(state_ef.params)
    assert len(res_leaves) == len(p_leaves)
    for r, p in zip(res_leaves, p_leaves):
        assert r.dtype == jnp.float32 and r.shape == p.shape
    assert state_fp.opt_state.residual is None


def test_opt_compression_mismatch_rejected(hvd):
    """init/update built under different modes fail loudly (the ZeRO
    state-owns-the-mode contract, on the DP plane): an ef16 update on a
    residual-less state, and the silent-residual-drop reverse pairing,
    both raise instead of crashing opaquely / quietly losing EF."""
    from horovod_tpu.opt import DistributedOptimizer

    params = {"w": jnp.ones((4,), jnp.float32)}
    grads = {"w": jnp.ones((4,), jnp.float32)}
    opt_ef = DistributedOptimizer(optax.sgd(0.1), compression="ef16")
    opt_plain = DistributedOptimizer(optax.sgd(0.1), compression=None)
    with pytest.raises(ValueError, match="compression mismatch"):
        opt_ef.update(grads, opt_plain.init(params), params)
    with pytest.raises(ValueError, match="compression mismatch"):
        opt_plain.update(grads, opt_ef.init(params), params)


def test_eager_allreduce_compressed_via_env(hvd, monkeypatch):
    """The eager plane consumes the live mode too: the engine compiles a
    compressed collective program (mode in the cache key) and small-int
    numerics stay exact through the f16 wire."""
    monkeypatch.setenv("HOROVOD_COMPRESSION", "fp16")
    x = np.full((4,), 3.0, np.float32)
    out = hvd.allreduce(x, op=hvd.Sum, name="compress.eager")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((4,), 3.0 * hvd.size()))
    assert out.dtype == jnp.float32
    from horovod_tpu.common.state import global_state

    keys = list(global_state().engine._program_cache)
    # Key order contract: (..., compression, hier) — hier stays last.
    assert any(k[0] == "grouped_allreduce" and k[-2] == "fp16"
               for k in keys), keys


# ---- error feedback: converge where plain fp16 stalls ----------------------


def _tiny_grad_loop(hvd, compression, steps=150):
    """SGD on 0.5*s*(w - 1)^2 with s chosen so every per-step gradient
    (~2.5e-8) rounds to ZERO in fp16 (below half the smallest f16
    subnormal): plain fp16 compression never moves w; error feedback
    accumulates the rounded-away gradient in the residual until it
    crosses the representable threshold and re-injects it.

    The whole loop runs inside ONE compiled program (fori_loop): jax
    0.4's CPU backend can deadlock its collective rendezvous when many
    tiny programs are dispatched in rapid succession alongside the
    engine's background threads — one dispatch sidesteps that entirely
    (and is what a real training loop's scan would do anyway)."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.opt import DistributedOptimizer

    mesh = hvd.mesh()
    s = 2.5e-8
    lr = 2e6
    dist_opt = DistributedOptimizer(optax.sgd(lr), compression=compression)
    w0 = {"w": jnp.zeros((16,), jnp.float32)}
    opt_state0 = dist_opt.init(w0)

    def run(params, opt_state):
        def body(_, carry):
            params, opt_state = carry
            grads = jax.tree_util.tree_map(
                lambda w: s * (w - 1.0), params)
            updates, opt_state = dist_opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        return jax.lax.fori_loop(0, steps, body, (params, opt_state))

    prog = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))
    params, _ = prog(w0, opt_state0)
    return np.asarray(params["w"])


def test_error_feedback_converges_where_fp16_stalls(hvd):
    w_fp16 = _tiny_grad_loop(hvd, "fp16")
    w_ef16 = _tiny_grad_loop(hvd, "ef16")
    w_none = _tiny_grad_loop(hvd, None)
    # Plain fp16: every quantized gradient is exactly zero -> bitwise no
    # movement. This is the stall, not merely slow progress.
    np.testing.assert_array_equal(w_fp16, np.zeros(16, np.float32))
    # Uncompressed converges (sanity that the problem itself moves).
    assert np.all(np.abs(w_none - 1.0) < 0.3), w_none[:4]
    # Error feedback recovers convergence to within the emission quantum.
    assert np.all(np.abs(w_ef16 - 1.0) < 0.3), w_ef16[:4]


# ---- hierarchical path ------------------------------------------------------


def test_hierarchical_compressed_allreduce(hvd):
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops import xla as hx

    hm = hvd.hierarchical_mesh()
    if hm is None:
        pytest.skip("no hierarchical mesh")
    n = hvd.size()
    rng = np.random.RandomState(0)
    data = rng.randint(0, 4, size=(n, 13)).astype(np.float32)  # f16-exact
    stacked = jnp.asarray(data).reshape(hm.devices.shape + (13,))
    sharded = jax.device_put(
        stacked, jax.sharding.NamedSharding(hm, P("dcn", "ici")))

    def fn(x):
        (out,) = hx.grouped_hierarchical_allreduce(
            [x[0, 0]], op=hx.Sum, compression="fp16")
        return out[None, None]

    prog = jax.jit(jax.shard_map(
        fn, mesh=hm, in_specs=P("dcn", "ici"),
        out_specs=P("dcn", "ici"), check_vma=False))
    out = np.asarray(prog(sharded)).reshape(n, 13)
    np.testing.assert_array_equal(out, data.sum(0, keepdims=True)
                                  .repeat(n, 0))
    hlo = prog.lower(sharded).compile().as_text()
    assert "f16" in hlo


# ---- wire-byte budgeting (fusion planner x compression) --------------------


def test_planner_budgets_compressed_wire_bytes():
    from horovod_tpu.common.fusion import leaf_wire_nbytes, plan_buckets_for

    class Leaf:
        def __init__(self, n, dtype):
            self.shape = (n,)
            self.dtype = jnp.dtype(dtype)

    f32 = Leaf(256, jnp.float32)
    bf16 = Leaf(256, jnp.bfloat16)
    i32 = Leaf(256, jnp.int32)
    # Uncompressed: fp32 wire everywhere (bf16 accumulates at fp32).
    assert leaf_wire_nbytes(f32) == 1024
    assert leaf_wire_nbytes(bf16) == 1024
    assert leaf_wire_nbytes(i32) == 1024
    comp = Compression.fp16
    # Compressed: floats at the 2-byte wire; ints untouched.
    assert leaf_wire_nbytes(f32, comp) == 512
    assert leaf_wire_nbytes(bf16, comp) == 512
    assert leaf_wire_nbytes(i32, comp) == 1024
    # The same cap therefore packs ~2x the parameters per bucket: 8
    # fp32 leaves under a 1024-byte cap -> 4 buckets uncompressed, 2
    # compressed. One threshold keeps meaning wire bytes.
    leaves = [Leaf(128, jnp.float32) for _ in range(8)]
    assert len(plan_buckets_for(leaves, 1024)) == 4
    assert len(plan_buckets_for(leaves, 1024, comp)) == 2


# ---- ZeRO: compressed reduce-scatter with sharded residuals ----------------


def _zero_problem(hvd, compression):
    from horovod_tpu.zero import init_zero_train_state, make_zero_train_step

    mesh = hvd.mesh()
    model = MLP3()
    opt = optax.sgd(0.1)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 16), jnp.float32)
    zstate = init_zero_train_state(model, opt, rng, sample, mesh,
                                   compression=compression)
    imgs = jnp.asarray(
        np.random.RandomState(0).rand(16, 16).astype(np.float32))
    lbls = jnp.asarray(
        np.random.RandomState(1).randint(0, 10, 16).astype(np.int32))
    imgs, lbls = shard_batch((imgs, lbls), mesh)
    zstep = make_zero_train_step(model, opt, mesh, donate=False,
                                 compression=compression)
    return zstep, zstate, imgs, lbls


def test_zero_compressed_scatter_element_type(hvd):
    zstep, zstate, imgs, lbls = _zero_problem(hvd, "fp16")
    zstate2, _ = zstep(zstate, imgs, lbls)
    prog = next(iter(zstep.cache.values()))
    hlo = prog.lower(zstate._replace(bucket_cap=None, stage=None), imgs,
                     lbls).compile().as_text()
    rs = collective_results(hlo, "reduce-scatter")
    assert rs, "no reduce-scatter in compiled ZeRO step"
    assert any(t == "f16" for t, _, _ in rs), rs
    # Master shard and optimizer state stay fp32.
    assert zstate2.pshard.dtype == jnp.float32


def test_zero_compressed_numerics_and_residual(hvd):
    zstep_n, zstate_n, imgs, lbls = _zero_problem(hvd, None)
    zstep_e, zstate_e, _, _ = _zero_problem(hvd, "ef16")
    assert zstate_n.residual is None
    assert zstate_e.residual is not None
    assert zstate_e.residual.dtype == jnp.float32
    for _ in range(2):
        zstate_n, loss_n = zstep_n(zstate_n, imgs, lbls)
        zstate_e, loss_e = zstep_e(zstate_e, imgs, lbls)
    assert abs(float(loss_n) - float(loss_e)) < 5e-2
    for pn, pe in zip(jax.tree_util.tree_leaves(zstate_n.params),
                      jax.tree_util.tree_leaves(zstate_e.params)):
        np.testing.assert_allclose(np.asarray(pn), np.asarray(pe),
                                   atol=5e-3, rtol=0)
    # The residual is live state: real-valued gradients quantized to f16
    # leave a nonzero rounding error somewhere.
    assert np.any(np.asarray(zstate_e.residual) != 0.0)


def test_zero_compression_mismatch_rejected(hvd):
    zstep_ef, _, imgs, lbls = _zero_problem(hvd, "ef16")
    _, zstate_plain, _, _ = _zero_problem(hvd, None)
    with pytest.raises(ValueError, match="compression mismatch"):
        zstep_ef(zstate_plain, imgs, lbls)
    zstep_plain, _, _, _ = _zero_problem(hvd, None)
    _, zstate_ef, _, _ = _zero_problem(hvd, "ef16")
    with pytest.raises(ValueError, match="compression mismatch"):
        zstep_plain(zstate_ef, imgs, lbls)


def test_zero_auto_step_follows_state_residual(hvd):
    """An "auto" step adopts ef16 from a residual-carrying state even
    when the ambient env says nothing (the state owns the mode, like the
    bucket cap owns the layout)."""
    from horovod_tpu.zero import make_zero_train_step

    zstep_ef, zstate_ef, imgs, lbls = _zero_problem(hvd, "ef16")
    mesh = hvd.mesh()
    zstep_auto = make_zero_train_step(MLP3(), optax.sgd(0.1), mesh,
                                      donate=False)  # compression="auto"
    s1, l1 = zstep_ef(zstate_ef, imgs, lbls)
    s2, l2 = zstep_auto(zstate_ef, imgs, lbls)
    assert float(l1) == float(l2)
    np.testing.assert_array_equal(np.asarray(s1.residual),
                                  np.asarray(s2.residual))


# ---- autotuner: compression on/off alongside the fusion threshold ----------


def test_autotune_compression_grid():
    from horovod_tpu.common.parameter_manager import ParameterManager

    applied = []
    pm = ParameterManager(
        core=None, warmup_samples=0, steps_per_sample=1, max_samples=3,
        compression_setter=applied.append,
        compression_candidates=("none", "bf16"))
    # Candidate 0 ("none") applied at construction.
    assert applied == ["none"]
    # Sample 1 scores "none"; tiny byte count -> low score.
    pm.update(nbytes=10)
    assert applied[-1] == "bf16"
    # Sample 2 scores "bf16"; huge byte count -> high score -> pinned.
    pm.update(nbytes=10 ** 9)
    assert pm.compression == "bf16"
    assert applied[-1] == "bf16"
    # The numeric GP phase proceeds afterwards (tuning still active).
    assert pm.active
