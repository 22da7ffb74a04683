#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the trainer still starts on the TPU.

    python3 chip_smoke.py            # one chip: the driver runs this
    python3 chip_smoke.py --chips 4  # one host, four chips: cross-chip only

Drives the main path once through the entry points a user calls —
``hvd.init()`` -> ``hvd.mesh()`` -> ``training.make_train_step`` on
ResNet-50 and ``models.transformer.make_train_step`` with the Pallas
attention kernels — at full width on random seeded weights, and checks
what comes out by the repo's own means (NumPy for the collectives, the
dense float32 attention for the kernels, the replicated optimizer for
ZeRO, the one-device step for the sharded decoder).

One process: it imports jax itself and starts no child that needs the
chip. Anything but a TPU is a failure, never a switch to the CPU. The
last line of stdout is one JSON object, ``{"ok": ..., "device": {...}}``;
the exit code is 0 only if every phase passed. The seconds, milliseconds
and bytes on the earlier lines are information, not benchmark results.

Tests import the phases and run them at toy sizes on the CPU backend
(tests/test_chip_smoke.py); the sizes here are the real ones.
"""

import argparse
import contextlib
import importlib.metadata
import json
import re
import sys
import time
import traceback

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common import native as hvd_native
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_train_step as make_decoder_step,
    shard_params, trained)
from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import (
    init_opt_state, init_train_state, make_train_step, replicate_state,
    shard_batch)
from horovod_tpu.zero import (
    gather_params, init_zero_train_state, make_zero_train_step)
from tools.compile_cache import enable_compile_cache

# The `gpt2s` cells' model (benchmark/configs/gpt2s.json): a GPT-2-small-class
# decoder at its published widths (d 768, 12 heads, 12 layers, vocabulary
# 50,304, 1,024 tokens), bf16, AdamW.
DECODER = dict(vocab=50304, d_model=768, n_heads=12, d_head=64, d_ff=3072,
               n_layers=12, max_seq=1024, dtype=jnp.bfloat16)
DECODER_BATCH = 8
# The `trinity-mini-t8192` cell's block at a tiny size: sliding-window and
# full attention layers, gated attention, a leading dense layer, a sigmoid
# router over 16 experts of which 4 are held, a shared expert, and the
# balancing bias that the step moves.
AFMOE = dict(vocab=4096, d_model=256, n_heads=4, d_head=64, n_kv_heads=2,
             d_ff=512, n_layers=3, max_seq=1024,
             layer_types=("sliding_attention", "full_attention",
                          "sliding_attention"), sliding_window=256,
             pos_table=False, use_moe=True, num_dense_layers=1,
             n_experts=16, n_experts_held=4, d_expert=256, moe_top_k=4,
             moe_score_func="sigmoid", norm_topk_prob=True,
             route_scale=2.826, n_shared_experts=1, expert_bias_rate=1e-3,
             norm="rmsnorm", qk_norm="head", attn_gate=True,
             post_norms=True, gated_mlp=True, embedding_multiplier=16.0,
             remat=True, remat_keeps=("flash_out", "flash_lse"),
             dtype=jnp.bfloat16)
# The `glm-4.7-flash-t8192` cell's block at a tiny size: latent attention
# with a shared rotated key head at the cell's head width (192 + 64 =
# 256, two lane tiles a row in the flash kernels), a leading dense layer,
# a sigmoid router over 16 experts of which 4 are held, a shared expert,
# the balancing bias, and one multi-token-prediction module in the loss.
GLM_LITE = dict(vocab=4096, d_model=256, n_heads=2, d_head=256, d_ff=512,
                n_layers=2, max_seq=1024,
                layer_types=("latent_attention",) * 2, q_lora_rank=96,
                kv_lora_rank=64, qk_nope_head_dim=192, qk_rope_head_dim=64,
                rope_theta=1e6, pos_table=False, use_moe=True,
                num_dense_layers=1, n_experts=16, n_experts_held=4,
                d_expert=256, moe_top_k=4, moe_score_func="sigmoid",
                norm_topk_prob=True, route_scale=1.8, n_shared_experts=1,
                expert_bias_rate=1e-3, norm="rmsnorm", gated_mlp=True,
                n_mtp_modules=1, remat=True,
                remat_keeps=("flash_out", "flash_lse", "mla_cq", "mla_ckv"),
                dtype=jnp.bfloat16)
# The `zaya1-8b-t16384` cell's block at a tiny size: CCA mixers at the
# cell's head width over grouped key/value heads, the MLP router whose
# state crosses layers over 8 experts of which 4 are held, one a token,
# learned residual scales, the balancing bias, and the tied head with the
# loss by blocks of tokens (one that does not divide them).
ZAYA = dict(vocab=4096, d_model=256, n_heads=4, n_kv_heads=2, d_head=128,
            n_layers=3, max_seq=1024, layer_types=("cca",) * 3,
            partial_rotary_factor=0.5, rope_theta=5e6, pos_table=False,
            use_moe=True, n_experts=8, n_experts_held=4, d_expert=256,
            moe_top_k=1, router_hidden=64, expert_bias_rate=1e-3,
            residual_scales=True, tie_embeddings=True, head_block=768,
            norm="rmsnorm", remat=True,
            remat_keeps=("flash_out", "flash_lse", "cca_q", "cca_kv"),
            dtype=jnp.bfloat16)
# Gradient bucket cap for the four-chip data-parallel step: ResNet-50's
# 102 MB of fp32 gradients in four buckets.
BUCKET_CAP_BYTES = 32 << 20

# Kernel cases [B, T, H, D] at the widths the decoder and the long-context
# jobs use. bf16 is what the trainer feeds the kernels; the float32 case,
# at jax's "highest" matmul precision, holds their logic (masks, online
# softmax, accumulation) to float32 tolerance. Tolerances are
# tests/test_pallas_attention.py's, per dtype.
KERNEL_CASES = [
    dict(shape=(2, 1024, 12, 64), dtype=jnp.bfloat16),
    dict(shape=(1, 2048, 8, 128), dtype=jnp.bfloat16),
    dict(shape=(2, 1024, 12, 64), dtype=jnp.bfloat16, segments=True),
    dict(shape=(1, 2048, 8, 128), dtype=jnp.bfloat16, window=512),
    dict(shape=(2, 1024, 12, 64), dtype=jnp.float32),
    # Grouped K/V ([B, T, kv_heads, D]): two query heads of a grid step on
    # one K/V head, and a group of eight (Trinity-Mini's, with its head
    # width, length and window): two chunks a side forward, and in the
    # fused backward eight steps of one head on a K/V head's whole
    # sequence of dK and dV.
    dict(shape=(2, 1024, 12, 64), dtype=jnp.float32, kv_heads=6),
    dict(shape=(1, 8192, 8, 128), dtype=jnp.bfloat16, kv_heads=1,
         window=2048),
    # GLM-4.7-Flash's head width and length: two lane tiles a row, chunks
    # of 4,096 forward and in the fused backward, which holds a head's dK
    # and dV whole (73 MiB of VMEM counted).
    dict(shape=(1, 8192, 2, 256), dtype=jnp.bfloat16),
]
KERNEL_TOL = {  # dtype name -> (forward, gradients), rtol == atol
    "bfloat16": (2e-2, 1e-1),
    "float32": (2e-5, 1e-4),
}
# tests/test_zero.py: ZeRO against the replicated optimizer.
ZERO_LOSS_ATOL = 1e-2
ZERO_PARAM_ATOL = 2e-2
# tests/test_transformer.py's loosest tolerance (gradients, rtol 5e-3),
# applied to the bf16 loss of the sharded step against one device.
DECODER_LOSS_RTOL = 5e-3


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


# ---- compile accounting ----------------------------------------------------

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILED = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_events = {}


def _watch_compiles():
    """Count jax's own compile events from here on. Every program jax
    lowers is then compiled or read from the persistent cache, so "no
    lowering after the first step" is "no compilation after it"."""
    if _events:
        return
    _events.update({_LOWERED: 0, _COMPILED: 0, _CACHE_HIT: 0,
                    "compile_s": 0.0})

    def on_duration(event, duration, **_):
        if event in (_LOWERED, _COMPILED):
            _events[event] += 1
        if event == _COMPILED:
            _events["compile_s"] += duration

    def on_event(event, **_):
        if event == _CACHE_HIT:
            _events[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def lowered_programs():
    _watch_compiles()
    return _events[_LOWERED]


def compile_step(phase, step, *args):
    """Compile the jitted ``step`` for ``args`` once, ahead of time.
    Returns the compiled step — which refuses arguments placed otherwise
    than it was compiled for, where a jitted call would quietly compile
    again — and its text."""
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    say(phase, f"compile_s={compile_s:.2f} program bytes per "
               f"device: temp={mem.temp_size_in_bytes} "
               f"arguments={mem.argument_size_in_bytes} "
               f"outputs={mem.output_size_in_bytes} "
               f"aliased={mem.alias_size_in_bytes}")
    return compiled, compiled.as_text()


def run_steps(phase, step_once, steps):
    """Call ``step_once() -> loss`` ``steps`` times, fenced by
    ``block_until_ready``. Checks: every loss finite, the last lower than
    the first (the batch is fixed), nothing lowered — so nothing compiled
    — after the first step. Returns the float losses."""
    losses, ms = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = step_once()
        loss.block_until_ready()
        ms.append(round(1e3 * (time.perf_counter() - t0), 2))
        losses.append(float(loss))
        if i == 0:
            lowered = lowered_programs()
    recompiled = lowered_programs() - lowered
    say(phase, f"step_ms={ms} losses={[round(x, 4) for x in losses]}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the fixed batch: {losses}")
    check(recompiled == 0,
          f"{recompiled} program(s) compiled after the first step")
    return losses


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---- phases ----------------------------------------------------------------

def read_device():
    """The device as jax reports it — the ``device`` of the last line."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(device, chips):
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu", "flax")}
    say("device", f"{device} versions={versions}")
    check(device["platform"] == "tpu",
          f"jax found no TPU (platform {device['platform']!r}); the smoke "
          f"never stands the CPU in for the chip")
    check(device["count"] == chips,
          f"--chips {chips} but jax reports {device['count']} device(s)")


def phase_native_core():
    """Build libhvdtpu.so from csrc (``make`` is the freshness check),
    load it, and bring ``hvd.init()`` up on it."""
    t0 = time.perf_counter()
    lib = hvd_native.load_library()  # raises if the build or load fails
    check(lib is not None,
          "native core not loaded: disabled by HOROVOD_NATIVE")
    say("native", f"libhvdtpu.so up to date with csrc and loaded in "
                  f"{time.perf_counter() - t0:.1f}s")
    hvd.init()
    check(hvd.metrics()["native"] is not None,
          "hvd.init() came up in direct mode, not on the native core")
    say("native", f"hvd.init(): native core, not direct mode; "
                  f"size={hvd.size()}")


def phase_eager():
    """Eager collectives through the engine on one different array per
    device, against NumPy."""
    n = hvd.size()
    rng = np.random.RandomState(0)
    xs = [rng.randn(3, 5).astype(np.float32) for _ in range(n)]
    for o in hvd.allreduce(xs, op=hvd.Sum, name="smoke.allreduce"):
        np.testing.assert_allclose(np.asarray(o), np.sum(xs, axis=0),
                                   rtol=1e-6, atol=1e-6)
    root = n - 1
    for o in hvd.broadcast(xs, root_rank=root, name="smoke.broadcast"):
        np.testing.assert_array_equal(np.asarray(o), xs[root])
    np.testing.assert_array_equal(
        np.asarray(hvd.allgather(xs, name="smoke.allgather")),
        np.concatenate(xs))
    hist = hvd.metrics()["native"]["histograms"]
    check(hist["enq_to_neg_allreduce_us"]["count"] > 0,
          "the allreduce did not go through the native engine")
    say("eager", f"allreduce/broadcast/allgather of {n} per-device "
                 f"array(s) match NumPy, negotiated by the native engine")


def _shard_devices(x):
    return {s.device for s in x.addressable_shards}


def _allreduce_group_sizes(hlo_text):
    """Participants per replica group of every all-reduce in compiled
    HLO text, in either spelling of ``replica_groups``: explicit
    ``{{0,1,2,3}}`` or iota ``[groups,size]<=[n]``."""
    sizes = []
    for m in re.finditer(r"all-reduce(?:-start)?\(.*?replica_groups="
                         r"(\{\{[\d,]*\}|\[\d+,(\d+)\]<=)", hlo_text):
        if m.group(2):
            sizes.append(int(m.group(2)))
        else:
            sizes.append(len(m.group(1).strip("{}").split(",")))
    return sizes


def _permute_ring_sizes(hlo_text):
    """Source-target pairs of every collective-permute in compiled HLO
    text: a ring over ``sp`` devices has ``sp`` of them."""
    return [m.group(1).count("},{") + 1 for m in re.finditer(
        r"collective-permute(?:-start)?\(.*?source_target_pairs="
        r"\{(\{.*?\})\}", hlo_text)]


def phase_resnet(model, batch_per_chip, image_size, steps,
                 bucket_cap_bytes="auto", num_classes=1000):
    """The data-parallel trainer over ``hvd.mesh()``, built as
    the ``resnet50`` cells build it (SGD+momentum, synthetic
    ImageNet-shaped batch). Returns what the ZeRO phase compares with."""
    mesh, n = hvd.mesh(), hvd.size()
    optimizer = optax.sgd(0.01, momentum=0.9)
    sample = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    state = replicate_state(
        init_train_state(model, optimizer, jax.random.PRNGKey(0), sample),
        mesh)
    images = np.random.RandomState(0).rand(
        batch_per_chip * n, image_size, image_size, 3).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, num_classes, size=(batch_per_chip * n,)).astype(np.int32)
    batch = shard_batch((images, labels), mesh)
    step = make_train_step(model, optimizer, mesh,
                           bucket_cap_bytes=bucket_cap_bytes)

    # Placement: code that has only met virtual devices may put
    # everything on the first one.
    check(len(_shard_devices(batch[0])) == n
          and batch[0].addressable_shards[0].data.shape[0] == batch_per_chip,
          f"the batch's shards are not spread over {n} device(s)")
    for leaf in jax.tree_util.tree_leaves(state.params):
        check(len(_shard_devices(leaf)) == n
              and leaf.addressable_shards[0].data.shape == leaf.shape,
              f"a parameter is not replicated on {n} device(s)")
    say("resnet", f"batch shards and every replicated parameter live on "
                  f"{n} distinct device(s)")
    step, text = compile_step("resnet", step, state, *batch)
    if n > 1:
        groups = _allreduce_group_sizes(text)
        check(groups and max(groups) == n,
              f"no all-reduce over {n} participants in the compiled "
              f"step (group sizes: {groups})")
        say("resnet", f"compiled step: {groups.count(n)} all-reduce(s) "
                      f"over {n} participants")

    params0 = jax.device_get(state.params)

    def step_once():
        nonlocal state
        state, loss = step(state, *batch)
        return loss

    losses = run_steps("resnet", step_once, steps)
    say("resnet", f"peak_bytes_in_use={peak_bytes()}")
    params = jax.device_get(state.params)
    check(any(np.any(a != b) for a, b in zip(
        jax.tree_util.tree_leaves(params0),
        jax.tree_util.tree_leaves(params))), "parameters did not change")
    for leaf in jax.tree_util.tree_leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(all(s.tobytes() == shards[0].tobytes() for s in shards[1:]),
              "replicas' parameters differ bitwise after the steps")
    if n > 1:
        say("resnet", f"{n} replicas' parameters bitwise equal after "
                      f"{steps} steps")
    return dict(model=model, batch=batch, sample=sample, steps=steps,
                bucket_cap_bytes=bucket_cap_bytes, losses=losses,
                params=params)


def phase_zero(stage, ref):
    """``zero.py`` at ``stage`` on the model and batch of
    :func:`phase_resnet`, against its replicated optimizer."""
    mesh = hvd.mesh()
    optimizer = optax.sgd(0.01, momentum=0.9)
    state = init_zero_train_state(
        ref["model"], optimizer, jax.random.PRNGKey(0), ref["sample"], mesh,
        bucket_cap_bytes=ref["bucket_cap_bytes"], zero_stage=stage)
    step = make_zero_train_step(ref["model"], optimizer, mesh,
                                zero_stage=stage)
    losses = []
    for _ in range(ref["steps"]):
        state, loss = step(state, *ref["batch"])
        losses.append(float(loss))
    say(f"zero{stage}", f"losses={[round(x, 4) for x in losses]}")
    np.testing.assert_allclose(losses, ref["losses"], rtol=0,
                               atol=ZERO_LOSS_ATOL)
    params = jax.device_get(gather_params(state, mesh))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=0,
                                   atol=ZERO_PARAM_ATOL)
    say(f"zero{stage}", "losses and parameters match the replicated "
                        "optimizer")


def phase_decoder(name, cfg, batch, steps, devices, sp=1, tp=1):
    """``models.transformer.make_train_step`` over a (dp, 1, sp, tp) mesh
    of ``devices``; the same seed gives every mesh the same weights and
    tokens. On the chip the compiled step must hold the Pallas kernels.
    Returns the losses."""
    mesh = build_parallel_mesh(devices, sp=sp, tp=tp, pp=1)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    params = shard_params(
        init_params(cfg, jax.random.PRNGKey(0), n_stages=1), cfg, mesh)
    optimizer = optax.adamw(3e-4)
    opt_state = init_opt_state(optimizer, trained(params), mesh)
    step = make_decoder_step(cfg, optimizer, mesh, n_microbatches=1)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab, (batch, cfg.max_seq)).astype(np.int32)
    data = NamedSharding(mesh, P("dp", "sp"))
    labels = jax.device_put(np.roll(tokens, -1, axis=1), data)
    tokens = jax.device_put(tokens, data)
    check(len(_shard_devices(tokens)) == len(devices),
          f"the tokens' shards are not spread over {len(devices)} device(s)")

    say(name, f"mesh={sizes} batch={batch}")
    step, text = compile_step(name, step, params, opt_state, tokens, labels)
    kernels = text.count("tpu_custom_call")
    rings = _permute_ring_sizes(text)
    say(name, f"compiled step: {kernels} tpu_custom_call, all-reduce "
              f"group sizes {sorted(set(_allreduce_group_sizes(text)))}, "
              f"{rings.count(sp)} collective-permute(s) around {sp}")
    if jax.default_backend() == "tpu":
        check(kernels > 0, "no tpu_custom_call in the compiled step: the "
                           "Pallas kernels were not taken")
    if sp > 1:
        check(sp in rings, f"no collective-permute around {sp} devices "
                           f"in the sp step: no ring (found {rings})")

    def step_once():
        nonlocal params, opt_state
        # (a step that moves a balancing bias returns its readings too)
        params, opt_state, loss, *_ = step(params, opt_state, tokens,
                                           labels)
        return loss

    losses = run_steps(name, step_once, steps)
    say(name, f"peak_bytes_in_use={peak_bytes()}")
    return losses


def phase_decoder_zaya(cfg, batch, steps, devices, tol=2e-3):
    """``phase_decoder`` on the ZAYA block, and the program against the
    plain float32 reference (``benchmark/reference_zaya.py``) on the same
    seeded weights and tokens: the first step's loss, relative."""
    from benchmark import reference_zaya

    losses = phase_decoder("decoder-zaya", cfg, batch, steps, devices)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab, (batch, cfg.max_seq)).astype(np.int32)
    model = dict(num_hidden_layers=cfg.n_layers, rms_norm_eps=cfg.norm_eps,
                 rope_theta=cfg.rope_theta,
                 rotated=int(cfg.partial_rotary_factor * cfg.d_head),
                 first_expert_held=cfg.first_expert_held)
    want = float(reference_zaya.step_readings(
        init_params(cfg, jax.random.PRNGKey(0), n_stages=1), tokens,
        np.roll(tokens, -1, axis=1), model)["loss"])
    err = abs(losses[0] - want) / want
    say("decoder-zaya", f"first loss {losses[0]:.6f} against the float32 "
                        f"reference's {want:.6f}: {err:.2e} (tol {tol:g})")
    check(err <= tol, f"the ZAYA block's first loss is {err:.2e} from the "
                      f"reference's, over {tol:g}")
    return losses


def dense_attention(q, k, v, window=None, seg=None):
    """Causal softmax attention in plain float32 ``jax.numpy``, the
    reference the kernels are held to (the same math as
    tests/test_pallas_attention.py's oracle). [B, T, H, D] in and out;
    k and v may have fewer heads, each shared by a group of q's."""
    T, D = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    iq = jnp.arange(T)[:, None]
    ik = jnp.arange(T)[None, :]
    allowed = (iq >= ik)[None, None]
    if window is not None:
        allowed = allowed & (iq - ik < window)[None, None]
    if seg is not None:
        allowed = allowed & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(allowed, s, -1e30), -1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))


def phase_kernels(cases):
    """``flash_attention`` forward and ``jax.grad`` (dq, dk, dv: the fused
    backward kernel at every case's shape) against
    :func:`dense_attention`, both on this backend (Mosaic on the chip)."""
    for case in cases:
        B, T, H, D = case["shape"]
        dtype, window = case["dtype"], case.get("window")
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(B, T, heads, D), dtype)
                   for heads in (H, *2 * (case.get("kv_heads", H),)))
        seg = None
        if case.get("segments"):
            # Three packed documents; the boundaries fall inside tiles.
            bounds = np.array([0.3 * T, 0.7 * T]).astype(int)
            seg = jnp.asarray(np.tile(
                np.searchsorted(bounds, np.arange(T), side="right"),
                (B, 1)), jnp.int32)

        def attend(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   q_segment_ids=seg, k_segment_ids=seg)

        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, window, seg) ** 2)

        # True float32 where float32 goes in: the TPU's default matmul
        # precision rounds float32 operands to bf16, in the kernels as
        # in XLA. Always for the reference; the bf16 cases run the
        # kernels as the trainer runs them.
        exact = jax.default_matmul_precision
        with (exact("highest") if dtype == jnp.float32
              else contextlib.nullcontext()):
            fwd = jax.jit(attend)
            if jax.default_backend() == "tpu":
                check("tpu_custom_call" in fwd.lower(q, k, v).as_text(),
                      "flash_attention did not lower to the Mosaic kernel")
            out = fwd(q, k, v)
            bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            if jax.default_backend() == "tpu":
                text = bwd.lower(q, k, v).as_text()
                check("flash_bwd" in text and "flash_dq" not in text,
                      "the backward is not the one fused kernel")
            grads = bwd(q, k, v)
        with exact("highest"):
            ref = jax.jit(dense_attention, static_argnums=3)(
                q, k, v, window, seg)
            ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
        tol_f, tol_g = KERNEL_TOL[jnp.dtype(dtype).name]
        label = (f"{list(case['shape'])} {jnp.dtype(dtype).name}"
                 + (f" over {k.shape[2]} K/V heads" if k.shape[2] < H else "")
                 + (" segments" if seg is not None else "")
                 + (f" window={window}" if window else ""))
        pairs = [("out", out, ref, tol_f)] + [
            (n, g, r, tol_g)
            for n, g, r in zip(("dq", "dk", "dv"), grads, ref_grads)]
        pairs = [(what, np.asarray(got, np.float32), np.asarray(want), tol)
                 for what, got, want, tol in pairs]
        say("kernels", f"{label}: max abs err " + " ".join(
            f"{what}={np.abs(got - want).max():.2e}"
            for what, got, want, _ in pairs)
            + f" (tol fwd {tol_f}, grads {tol_g})")
        for what, got, want, tol in pairs:
            check(np.abs(got).max() > 0, f"{label}: {what} is all zero")
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                       err_msg=f"{label}: {what}")


def phase_decoder_parallel(cfg, batch, steps, devices):
    """The decoder on dp 2 x tp 2 and on sp 4 (ring attention: the block
    kernels plus the permutes) over four ``devices``, against the same
    seeded step on the first of them."""
    one = phase_decoder("decoder-1dev", cfg, batch, steps, devices[:1])
    for name, axes in (("decoder-dp2tp2", dict(tp=2)),
                       ("decoder-sp4", dict(sp=4))):
        got = phase_decoder(name, cfg, batch, steps, devices, **axes)
        np.testing.assert_allclose(
            got, one, rtol=DECODER_LOSS_RTOL,
            err_msg=f"{name} loss vs one device")
        say(name, f"losses within rtol {DECODER_LOSS_RTOL} of one device")


# ---- driver ----------------------------------------------------------------

def one_chip_phases():
    resnet = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    return [
        ("eager", phase_eager),
        ("resnet", lambda: phase_resnet(resnet, 32, 224, steps=5)),
        ("decoder", lambda: phase_decoder(
            "decoder", TransformerConfig(**DECODER), DECODER_BATCH, 3,
            jax.devices())),
        ("decoder-afmoe", lambda: phase_decoder(
            "decoder-afmoe", TransformerConfig(**AFMOE), 2, 3,
            jax.devices())),
        ("decoder-glm-lite", lambda: phase_decoder(
            "decoder-glm-lite", TransformerConfig(**GLM_LITE), 2, 3,
            jax.devices())),
        ("decoder-zaya", lambda: phase_decoder_zaya(
            TransformerConfig(**ZAYA), 2, 3, jax.devices())),
        ("kernels", lambda: phase_kernels(KERNEL_CASES)),
    ]


def four_chip_phases():
    resnet = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    ref = {}

    def dp():
        ref.update(phase_resnet(resnet, 32, 224, steps=3,
                                bucket_cap_bytes=BUCKET_CAP_BYTES))

    return [
        ("eager", phase_eager),
        ("resnet", dp),
        ("zero2", lambda: phase_zero(2, ref)),
        ("zero3", lambda: phase_zero(3, ref)),
        ("decoder-parallel", lambda: phase_decoder_parallel(
            TransformerConfig(**DECODER), DECODER_BATCH, 3, jax.devices())),
    ]


def _report_compiles(cache_dir):
    """What the run compiled and what it read back. Run the smoke twice
    in one chip call and the second prints lower ``compile_s`` for each
    step and more programs read from the cache."""
    say("cache", f"{_events[_COMPILED]} program(s) compiled in "
                 f"{_events['compile_s']:.1f}s, {_events[_CACHE_HIT]} read "
                 f"from the compile cache at {cache_dir}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=[1, 4],
                        help="4: the cross-chip phases only, one process "
                             "driving the four chips of one host")
    args = parser.parse_args(argv)

    cache_dir = enable_compile_cache()
    say("cache", f"compile cache at {cache_dir}")
    _watch_compiles()
    device = read_device()
    failed = []
    try:
        phase_device(device, args.chips)
        phase_native_core()
    except Exception:
        # Nothing below can run without the chip and hvd.init().
        traceback.print_exc()
        failed.append("device/native")
    else:
        phases = one_chip_phases() if args.chips == 1 else four_chip_phases()
        for name, phase in phases:
            try:
                phase()
            except Exception:
                # Reported and counted, never swallowed: the later phases
                # still run so one chip call shows every fault.
                traceback.print_exc()
                say(name, "FAILED")
                failed.append(name)
        hvd.shutdown()
        _report_compiles(cache_dir)
    if failed:
        say("smoke", f"failed phases: {failed}")
    print(json.dumps({"ok": not failed, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
