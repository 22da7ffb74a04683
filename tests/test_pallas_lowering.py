"""Mosaic lowering smoke tests (VERDICT r3 #4): the Pallas kernels are
numerically verified in interpret mode, but a kernel that no longer
*compiles* for TPU would only surface on hardware. ``jax.export`` with
``platforms=["tpu"]`` runs the actual Mosaic lowering pipeline on a CPU
host — the exported module must contain the ``tpu_custom_call`` carrying
the serialized kernel, so lowering regressions fail here, in CI, without
a chip."""

import hashlib
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.export  # noqa: E402,F401  (not auto-imported on older jax)
import jax.numpy as jnp  # noqa: E402

import horovod_tpu.ops.pallas_attention as pa  # noqa: E402


@pytest.fixture
def mosaic(monkeypatch):
    """Force the Mosaic path (use_pallas=True, interpret=False) even on
    the CPU test host — export lowers for the TPU target platform."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))


def _export_tpu(fn, *args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    return exp.mlir_module()


def _qkv(B=1, T=1024, H=2, D=128, dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D), dtype)  # noqa: E731
    return mk(), mk(), mk()


def test_flash_attention_fwd_lowers_to_mosaic(mosaic):
    q, k, v = _qkv()
    txt = _export_tpu(
        lambda q, k, v: pa.flash_attention(q, k, v, causal=True), q, k, v)
    assert "tpu_custom_call" in txt


def test_flash_attention_bwd_lowers_to_mosaic(mosaic):
    """The backward kernel's Mosaic lowering, beside the forward's."""
    q, k, v = _qkv()

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    txt = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    # Forward (rematerialized for residuals) + the fused backward.
    assert txt.count("tpu_custom_call") == 2 and "flash_bwd" in txt


def test_flash_attention_two_pass_bwd_lowers_to_mosaic(mosaic, monkeypatch):
    """The fall-back for a sequence whose dK and dV do not fit the fused
    kernel's budget: the two passes lower too."""
    monkeypatch.setattr(pa, "BWD_VMEM_BUDGET", 0)
    q, k, v = _qkv()

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    txt = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert txt.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in txt


def _block_programs(level):
    """(function, arguments) of the per-block state and of its gradients
    at T 512: through the public functions on [B, T, H, D], or through the
    merged-operand ones on [B H, T, D] called directly."""
    q, k, v = _qkv(T=512)
    B, T, H, D = q.shape
    do = jnp.ones_like(q)
    if level == "merged":
        q, k, v, do = (pa._merge_heads(x) for x in (q, k, v, do))
        offs = jnp.zeros((2,), jnp.int32)
        stat = jnp.zeros((B * H, T, 1), jnp.float32)

        def fwd(q, k, v):
            return pa.flash_attention_block_merged(q, k, v, offs,
                                                   causal=True)

        def bwd(q, k, v, do, lse, delta):
            return pa.flash_attention_block_grads_merged(
                q, k, v, do, lse, delta, offs, causal=True)
    else:
        stat = jnp.zeros((B, H, T), jnp.float32)

        def fwd(q, k, v):
            return pa.flash_attention_block(q, k, v, q_off=0, k_off=0,
                                            causal=True)

        def bwd(q, k, v, do, lse, delta):
            return pa.flash_attention_block_grads(
                q, k, v, do, lse, delta, q_off=0, k_off=0, causal=True)

    return {"state": (fwd, (q, k, v)),
            "grads": (bwd, (q, k, v, do, stat, stat))}


@pytest.mark.parametrize("level", ["heads", "merged"])
def test_ring_attention_block_kernels_lower_to_mosaic(mosaic, level):
    """The ring-attention per-block state/grad kernels lower too, and so
    do the merged-operand functions under them that EVA attention
    calls."""
    for fn, args in _block_programs(level).values():
        assert "tpu_custom_call" in _export_tpu(fn, *args)


def _lowered_without_kernel_bodies(fn, *args):
    """The text ``fn`` lowers to for a TPU, with no debug locations and
    each Mosaic call's serialized body cut out: a body carries the file
    paths and line numbers of the kernel's source, so it differs between
    two checkouts of one program. What is left is every operation around
    the kernels with its types, and each call's name, operand and result
    layouts and scoped memory."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    cut, n = re.subn(r'(\\22body\\22: \\22)[^\\]*', r"\1", text)
    assert n == text.count("tpu_custom_call") > 0
    return cut


# sha256 (first 16 hex digits) of ``_lowered_without_kernel_bodies`` of the
# two public block functions, read with it on the commit before they became
# merge -> the merged-operand function -> split (PR 45's tree, jax 0.9.0):
# ring attention's programs are what they were.
BLOCK_FUNCTIONS = {"state": "38626217b64fbfa0", "grads": "59076b9b1fc33862"}


@pytest.mark.parametrize("which", sorted(BLOCK_FUNCTIONS))
def test_the_public_block_functions_lower_to_the_text_they_lowered_to(
        mosaic, which):
    fn, args = _block_programs("heads")[which]
    got = hashlib.sha256(_lowered_without_kernel_bodies(fn, *args).encode()
                         ).hexdigest()[:16]
    assert got == BLOCK_FUNCTIONS[which], (
        f"flash_attention_block{'_grads' if which == 'grads' else ''} "
        f"lowers to another program than on the commit this hash was read "
        f"on ({got} != {BLOCK_FUNCTIONS[which]})")


def test_segment_id_kernels_lower_to_mosaic(mosaic):
    """The segment-tiled variants (packed sequences) must lower too —
    they stream (1, block) int32 id tiles next to the Q/K/V tiles, a
    layout Mosaic has to accept in the forward AND the backward kernel."""
    q, k, v = _qkv()
    B, T = q.shape[:2]
    seg = jnp.zeros((B, T), jnp.int32)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg,
            k_segment_ids=seg).astype(jnp.float32).sum()

    txt = _export_tpu(loss, q, k, v)
    assert "tpu_custom_call" in txt
    txt = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert txt.count("tpu_custom_call") >= 2


def test_segment_id_kernels_lower_with_small_blocks(mosaic):
    """Sub-128 tiles (T=192 -> block 64): the row-oriented (1, block, 1)
    id layout must lower where a lane-major (1, 1, block) tile fails
    Mosaic's (8, 128)-divisibility rule."""
    q, k, v = _qkv(T=192)
    seg = jnp.zeros((q.shape[0], q.shape[1]), jnp.int32)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg,
            k_segment_ids=seg).astype(jnp.float32).sum()

    txt = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert txt.count("tpu_custom_call") >= 2


# The benchmark's six decoder cells, batch cut: (B, T, H, Hkv, D, window).
_CELL_SHAPES = {"gpt2s-t128": (4, 128, 12, 12, 64, None),
                "gpt2s-t1024": (1, 1024, 12, 12, 64, None),
                "olmoe-t4096": (1, 4096, 16, 16, 128, None),
                "granite-h-t8192": (1, 8192, 8, 2, 64, None),
                "trinity-mini-t8192": (1, 8192, 8, 1, 128, 2048),
                "glm-4.7-flash-t8192": (1, 8192, 2, 2, 256, None)}


@pytest.mark.parametrize("segments", [False, True])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_cell_shapes_lower_to_mosaic(mosaic, cell, segments):
    """The grid step ``kernel_plan`` picks at each cell's shape (several
    heads a step at T 128, the whole sequence resident at T 4,096, chunks
    and a group's heads on the sequential dimensions at T 8,192, the
    in-kernel walk with its run-time bounds) lowers in the forward and in
    the fused backward: a step Mosaic refuses fails here, off the
    chip."""
    B, T, H, Hkv, D, window = _CELL_SHAPES[cell]
    q, k, v = _qkv(B=B, T=T, H=H, D=D)
    k, v = k[:, :, :Hkv], v[:, :, :Hkv]
    seg = jnp.zeros((B, T), jnp.int32) if segments else None

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg, k_segment_ids=seg,
            window=window).astype(jnp.float32).sum()

    txt = _export_tpu(loss, q, k, v)
    assert txt.count("tpu_custom_call") == 1 and "flash_fwd" in txt
    txt = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert txt.count("tpu_custom_call") == 2
    assert "flash_fwd" in txt and "flash_bwd" in txt
    assert "flash_dq" not in txt and "flash_dkv" not in txt
