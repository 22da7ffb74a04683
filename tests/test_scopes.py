"""The compiled step names its own work (docs/diagnostics.md, "Tracing"):
the three step builders put a fixed vocabulary of ``jax.named_scope``
names into every instruction's ``op_name`` and name their jitted module,
and each flash kernel carries its name. Read from the compiled text and
the jaxpr on the CPU; the device trace that reads the same names is the
benchmark's (``benchmark/scope_reduce.py``)."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops import pallas_attention as pa

BUILDERS = {
    # builder: (module name, scopes beside forward / backward / optimizer)
    "dp": ("jit_hvd_dp_step", ("exchange", "loss")),
    "decoder": ("jit_hvd_decoder_step",
                ("embed", "attention", "mlp", "head", "loss")),
    "decoder_moe": ("jit_hvd_decoder_step",
                    ("embed", "attention", "moe", "moe_route", "moe_dispatch",
                     "moe_experts", "moe_combine", "head", "loss")),
    "zero": ("jit_hvd_zero_step", ("loss",)),
}


@pytest.fixture(scope="module")
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.BatchNorm(use_running_average=not train)(
                nn.relu(nn.Dense(32)(x)))
            return nn.Dense(10)(x)

    return MLP()


def _images(hvd):
    from horovod_tpu.training import shard_batch

    n = 2 * hvd.size()
    rng = np.random.RandomState(0)
    return shard_batch(
        (jnp.asarray(rng.rand(n, 8, 8, 3).astype(np.float32)),
         jnp.asarray(rng.randint(0, 10, n).astype(np.int32))), hvd.mesh())


def _build(kind, hvd):
    """(run, lower) of one step builder at a size the CPU compiles in a
    second or two: ``run()`` takes one step and returns its outputs,
    ``lower()`` lowers the jitted program."""
    if kind in ("decoder", "decoder_moe"):
        from horovod_tpu.models.transformer import (
            TransformerConfig, init_params, make_train_step, shard_params)
        from horovod_tpu.parallel.mesh import build_parallel_mesh
        from horovod_tpu.training import init_opt_state

        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, d_head=16,
                                d_ff=64, n_layers=2, max_seq=16)
        if kind == "decoder_moe":  # OLMoE's block, experts over dp 2
            cfg = dataclasses.replace(
                cfg, use_moe=True, n_experts=4, d_expert=16, moe_top_k=2,
                norm="rmsnorm", qk_norm=True, rope=True,
                router_aux_loss_coef=0.01, router_z_loss_coef=0.001)
        mesh = build_parallel_mesh(jax.devices()[:2], sp=1, tp=1, pp=1)
        opt = optax.adamw(1e-3)
        params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1),
                              cfg, mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        step = make_train_step(cfg, opt, mesh, n_microbatches=1)
        args = (params, init_opt_state(opt, params, mesh), tokens,
                jnp.roll(tokens, -1, axis=1))
        # make_train_step donates: the step gets copies.
        return (lambda: step(*jax.tree_util.tree_map(jnp.copy, args)),
                lambda: step.lower(*args))
    model, opt = _mlp(), optax.sgd(0.1, momentum=0.9)
    sample = jnp.zeros((1, 8, 8, 3), jnp.float32)
    images, labels = _images(hvd)
    if kind == "dp":
        from horovod_tpu.training import (
            init_train_state, make_train_step, replicate_state)

        state = replicate_state(
            init_train_state(model, opt, jax.random.PRNGKey(0), sample),
            hvd.mesh())
        step = make_train_step(model, opt, hvd.mesh(), donate=False)
        return (lambda: step(state, images, labels),
                lambda: step.lower(state, images, labels))
    from horovod_tpu.zero import init_zero_train_state, make_zero_train_step

    state = init_zero_train_state(model, opt, jax.random.PRNGKey(0), sample,
                                  hvd.mesh(), zero_stage=2)
    step = make_zero_train_step(model, opt, hvd.mesh(), donate=False)

    def lower():
        step(state, images, labels)  # the program is in step.cache now
        (program,) = step.cache.values()
        return program.lower(state._replace(bucket_cap=None, stage=None),
                             images, labels)

    return lambda: step(state, images, labels), lower


@pytest.fixture(scope="module")
def compiled_text(hvd):
    texts = {}

    def text_of(kind):
        if kind not in texts:
            _, lower = _build(kind, hvd)
            texts[kind] = lower().compile().as_text()
        return texts[kind]

    return text_of


def _op_names(text):
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("kind", BUILDERS)
def test_the_jitted_module_has_its_stable_name(compiled_text, kind):
    module, _ = BUILDERS[kind]
    assert re.match(rf"HloModule {module}\b", compiled_text(kind))


@pytest.mark.parametrize("kind", BUILDERS)
def test_the_compiled_step_carries_the_scopes(compiled_text, kind):
    module, scopes = BUILDERS[kind]
    step = f"jit({module[4:]})/"
    names = {n + "/" for n in _op_names(compiled_text(kind))
             if n.startswith(step)}
    forward = {n for n in names if "/jvp(forward)/" in n}
    backward = {n for n in names if "/transpose(jvp(forward))/" in n}
    assert forward and backward
    for scope in ("optimizer",) + scopes:
        assert any(f"/{scope}/" in n for n in names), scope
    # Nothing of a pass is also under the classes that are told apart
    # from it first (benchmark/scope_reduce.py's precedence).
    for n in forward | backward:
        assert "/optimizer/" not in n and "/exchange/" not in n


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.mark.parametrize("kind", BUILDERS)
def test_the_scopes_change_nothing_but_names(hvd, monkeypatch, kind):
    """Bit-equal outputs from a build in which ``jax.named_scope`` does
    nothing (the scopes of the attention module's XLA twins are written
    when it is imported and stay): scopes are metadata."""
    run, _ = _build(kind, hvd)
    with_scopes = run()
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    run, lower = _build(kind, hvd)
    names = _op_names(lower().compile().as_text())
    assert not any("forward" in n or "optimizer" in n for n in names)
    without = run()
    for a, b in zip(jax.tree_util.tree_leaves(with_scopes),
                    jax.tree_util.tree_leaves(without)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_Q = jax.ShapeDtypeStruct((2, 16, 8), jnp.float32)
_ROW = jax.ShapeDtypeStruct((2, 16, 1), jnp.float32)
_OFFS = jax.ShapeDtypeStruct((2,), jnp.int32)
def _two_passes(*args, **kw):
    """``_pallas_bwd`` where the fused kernel has no plan: a budget of
    nothing, as a sequence too long for it finds."""
    budget, pa.BWD_VMEM_BUDGET = pa.BWD_VMEM_BUDGET, 0
    try:
        return pa._pallas_bwd(*args, **kw)
    finally:
        pa.BWD_VMEM_BUDGET = budget


PALLAS_SITES = {
    "block_state": (
        lambda q, offs: pa._flash_forward(q, q, q, offs, True, True,
                                          "state"),
        (_Q, _OFFS), ["flash_fwd"]),
    "forward": (
        lambda q, offs: pa._flash_forward(q, q, q, offs, True, True,
                                          "plain"),
        (_Q, _OFFS), ["flash_fwd"]),
    "forward_train": (
        lambda q, offs: pa._flash_forward(q, q, q, offs, True, True,
                                          "train"),
        (_Q, _OFFS), ["flash_fwd"]),
    "backward": (
        lambda q, row, offs: pa._pallas_bwd(q, q, q, q, row, row, offs,
                                            True, True),
        (_Q, _ROW, _OFFS), ["flash_bwd"]),
    "segmented_backward": (
        lambda q, row, offs: pa._pallas_bwd(
            q, q, q, q, row, row, offs, True, True,
            q_seg=jnp.zeros((2, 16), jnp.int32),
            k_seg=jnp.zeros((2, 16), jnp.int32)),
        (_Q, _ROW, _OFFS), ["flash_bwd"]),
    "two_pass_backward": (
        lambda q, row, offs: _two_passes(q, q, q, q, row, row, offs,
                                         True, True),
        (_Q, _ROW, _OFFS), ["flash_dq", "flash_dkv"]),
}


@pytest.mark.parametrize("site", PALLAS_SITES)
def test_each_pallas_call_carries_its_kernel_name(site):
    """The name goes to Mosaic and, as a scope, into the call's
    ``op_name``. Read from the jaxpr; nothing is run."""
    fn, shapes, want = PALLAS_SITES[site]
    calls = [eqn for eqn in jax.make_jaxpr(fn)(*shapes).eqns
             if eqn.primitive.name == "pallas_call"]
    assert [eqn.params["name"] for eqn in calls] == want
    assert [str(eqn.source_info.name_stack) for eqn in calls] == want
