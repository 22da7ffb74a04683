"""chip_smoke.py is the proof every later tree must give on the chip; this
file keeps it from rotting between chip runs, without the chip:

- its phases, imported and run at toy sizes on the CPU mesh (kernels
  interpreted) — wrong paths, arguments, meshes and sharding rules;
- the program refusing to stand the CPU in for the chip (chip_smoke.py,
  benchmark/run.py, the peak table);
- the compile-cache helper;
- the attention kernels compiled for a *described* v5e chip at the real
  widths: what the chip's compiler would refuse fails here.

Nothing here is a chip result.
"""

import functools
import json
import os
import re
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import horovod_tpu.ops.pallas_attention as pa  # noqa: E402
from benchmark import flops  # noqa: E402
from horovod_tpu.common.state import AXIS_GLOBAL  # noqa: E402
from tools import compile_cache  # noqa: E402

BENCHMARK_RUN = ("benchmark/run.py", "--workload", "gpt2s-t128", "--seed",
                 "1", "--seconds", "1")


def _run(script, *args):
    from conftest import subprocess_cpu_env

    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, script), *args], cwd=REPO,
        env=subprocess_cpu_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


@pytest.fixture(scope="module", autouse=True)
def no_chip_runs():
    """Both programs started on the CPU backend at once, so they run
    while the other tests do; each test below waits for its own."""
    procs = {cmd[0]: _run(*cmd)
             for cmd in (("chip_smoke.py",), BENCHMARK_RUN)}
    yield procs
    for p in procs.values():
        p.kill()
        p.wait(timeout=30)


# ---- the phases at toy sizes -----------------------------------------------

class TinyNet(nn.Module):
    """Conv + BatchNorm + Dense: the state shapes of ResNet (parameters
    and batch statistics) at a size the CPU compiles in a second."""

    @nn.compact
    def __call__(self, x, train=True):
        x = nn.Conv(8, (3, 3), use_bias=False)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
        x = jnp.mean(nn.relu(x), axis=(1, 2))
        return nn.Dense(10)(x)


@pytest.fixture(scope="module")
def smoke_world():
    """hvd.init() over four virtual devices, through the smoke's own
    native-core phase (it asserts the core loaded and is not direct
    mode)."""
    import horovod_tpu as hvd

    hvd.init(devices=jax.devices()[:4])
    chip_smoke.phase_native_core()
    yield hvd
    hvd.shutdown()


def test_device_phase_refuses_cpu():
    device = chip_smoke.read_device()
    assert device["platform"] == "cpu"
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.phase_device(device, chips=1)


def test_eager_phase(smoke_world):
    chip_smoke.phase_eager()


def test_trainer_and_zero_phases(smoke_world):
    """Data-parallel step with the bucketed allreduce (placement over
    four devices, an all-reduce over four participants, bitwise-equal
    replicas), then ZeRO 2 and 3 against it."""
    ref = chip_smoke.phase_resnet(TinyNet(), 2, 8, steps=3,
                                  bucket_cap_bytes=256, num_classes=10)
    assert len(ref["losses"]) == 3
    chip_smoke.phase_zero(2, ref)
    chip_smoke.phase_zero(3, ref)


def test_collective_shapes_read_from_hlo_text():
    text = (
        "%ar = f32[8] all-reduce(f32[8] %x), channel_id=1, "
        "replica_groups=[1,4]<=[4], use_global_device_ids=true\n"
        "%ar2 = f32[8] all-reduce-start(f32[8] %y), "
        "replica_groups={{0,1},{2,3}}, to_apply=%add\n"
        "%done = f32[8] all-reduce-done(f32[8] %ar2)\n"
        "%cp = bf16[8] collective-permute-start(bf16[8] %z), channel_id=3, "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n"
        "%cp2 = f32[2] collective-permute(f32[2] %w), "
        "source_target_pairs={{0,0}}\n")
    assert chip_smoke._allreduce_group_sizes(text) == [4, 2]
    assert chip_smoke._permute_ring_sizes(text) == [4, 1]


TOY_DECODER = chip_smoke.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, d_head=8, d_ff=64, n_layers=2,
    max_seq=32, dtype=jnp.bfloat16)


def test_decoder_phase_one_device():
    chip_smoke.phase_decoder("toy-decoder", TOY_DECODER, 4, 3,
                             jax.devices()[:1])


def test_decoder_phase_with_the_afmoe_block():
    """The smoke's `decoder-afmoe` phase at a toy size: the step that
    moves a balancing bias returns four results, and its optimizer state
    is made for the trained leaves alone."""
    import dataclasses

    toy = dataclasses.replace(
        chip_smoke.TransformerConfig(**chip_smoke.AFMOE), vocab=64,
        d_model=32, n_heads=4, d_head=8, d_ff=64, d_expert=16, max_seq=32,
        sliding_window=8, embedding_multiplier=32 ** 0.5)
    losses = chip_smoke.phase_decoder("toy-afmoe", toy, 2, 3,
                                      jax.devices()[:1])
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_decoder_phase_with_the_glm_lite_block():
    """The smoke's `decoder-glm-lite` phase at a toy size: latent
    attention layers and a multi-token-prediction module in a step that
    moves two balancing biases."""
    import dataclasses

    toy = dataclasses.replace(
        chip_smoke.TransformerConfig(**chip_smoke.GLM_LITE), vocab=64,
        d_model=32, d_head=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
        q_lora_rank=12, kv_lora_rank=8, d_ff=64, d_expert=16, max_seq=32)
    losses = chip_smoke.phase_decoder("toy-glm-lite", toy, 2, 3,
                                      jax.devices()[:1])
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_decoder_phase_with_the_zaya_block():
    """The smoke's `decoder-zaya` phase at a toy size: CCA layers, the MLP
    router's carried state, residual scales and the head by blocks in a
    step that moves a balancing bias, and the first loss against the
    plain reference's."""
    import dataclasses

    toy = dataclasses.replace(
        chip_smoke.TransformerConfig(**chip_smoke.ZAYA), vocab=64,
        d_model=32, d_head=16, d_expert=16, router_hidden=8, max_seq=32,
        head_block=24, dtype=jnp.float32)
    losses = chip_smoke.phase_decoder_zaya(toy, 2, 3, jax.devices()[:1],
                                           tol=1e-5)
    assert len(losses) == 3 and losses[-1] < losses[0]


@pytest.mark.full
def test_decoder_parallel_phase(monkeypatch):
    """dp 2 x tp 2 and sp 4 (ring, block kernels interpreted) against one
    device, on four virtual devices."""
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    chip_smoke.phase_decoder_parallel(TOY_DECODER, 4, 3, jax.devices()[:4])


@pytest.mark.parametrize("case", [
    dict(shape=(1, 64, 2, 8), segments=True, window=16),
    dict(shape=(2, 64, 4, 8), kv_heads=2, window=16),
], ids=["multi-head", "grouped"])
def test_kernels_phase_interpreted(monkeypatch, case):
    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    chip_smoke.phase_kernels([dict(case, dtype=jnp.float32)])


def test_kernels_phase_catches_a_wrong_kernel(monkeypatch):
    """The comparison has teeth: a kernel that ignores the causal mask
    fails it."""
    monkeypatch.setattr(
        chip_smoke, "flash_attention",
        lambda q, k, v, causal, **kw: pa.flash_attention(
            q, k, v, causal=False, use_pallas=False))
    with pytest.raises(AssertionError):
        chip_smoke.phase_kernels(
            [dict(shape=(1, 64, 2, 8), dtype=jnp.float32)])


# ---- no CPU stand-in on the device path ------------------------------------

def test_chip_smoke_without_a_chip_fails(no_chip_runs):
    out, err = no_chip_runs["chip_smoke.py"].communicate(timeout=120)
    assert no_chip_runs["chip_smoke.py"].returncode != 0, out + err
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "resnet" not in out  # no phase ran on the CPU


def test_benchmark_without_a_chip_prints_no_result(no_chip_runs):
    run = no_chip_runs["benchmark/run.py"]
    out, err = run.communicate(timeout=120)
    assert run.returncode == 3, out + err  # harness.NoChip
    assert "{" not in out  # no result line
    assert "no TPU" in err


def test_peaks_unknown_device_raises():
    with pytest.raises(ValueError, match="no peaks"):
        flops.peaks_for("unknown")
    # What the installed runtime reports for a v5e chip.
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


# ---- the compile cache helper ----------------------------------------------

@pytest.fixture
def cache_config():
    """Leave jax's cache configuration as this test found it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_cache_helper_sets_nothing_when_the_variable_is_set(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_sets_the_fixed_path_otherwise(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    # Fixed means fixed: a second call names the same directory, and
    # git ignores it.
    assert compile_cache.enable_compile_cache() == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---- compiles for the described chip ---------------------------------------

@pytest.fixture(scope="module")
def described_topology():
    """A described (not attached) v5e 2x2, with the persistent cache off
    around the compiles: such an entry could not be read back without a
    chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def described_chip(described_topology):
    """One device of the described v5e 2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(described_topology.devices[0])


@pytest.fixture(scope="module")
def described_host(described_topology):
    """The four chips of the described host as the data-parallel mesh."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(described_topology.devices), (AXIS_GLOBAL,))


def _attention(variant):
    """(function of q, k, v; Mosaic kernels its program must hold)."""
    def attend(q, k, v):
        seg = (jnp.zeros(q.shape[:2], jnp.int32)
               if variant == "segments" else None)
        return pa.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg, k_segment_ids=seg,
            window=512 if variant == "window" else None)

    if variant == "forward":
        return attend, ("flash_fwd",)
    return jax.grad(lambda q, k, v: attend(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2)), ("flash_fwd", "flash_bwd")


@pytest.mark.parametrize("shape", [(2, 1024, 12, 64), (1, 2048, 8, 128),
                                   (1, 8192, 4, 256)],
                         ids=["D64-T1024", "D128-T2048", "D256-T8192"])
@pytest.mark.parametrize("variant",
                         ["forward", "backward", "segments", "window"])
def test_attention_kernels_compile_for_v5e(described_chip, monkeypatch,
                                           variant, shape):
    """Forward, and the fused backward kernel plain, with segment ids
    and with a window, at the smoke's widths and at GLM-4.7-Flash's head
    width and length (chunks of 4,096 near the two VMEM budgets)."""
    # default_backend() is the CPU here; steer the dispatch to Mosaic.
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    fn, kernels = _attention(variant)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=described_chip)
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert sorted(_flash_custom_calls(text)) == sorted(kernels)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_compile_for_v5e_at_highest_precision(
        described_chip, monkeypatch, dtype):
    """Under ``jax.default_matmul_precision("highest")`` float32 operands
    take the multi-pass float32 MXU product (``_mxu_dot``) — its VMEM fit
    is the chip compiler's to judge — and bf16 operands stay as they
    are."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    fn, kernels = _attention("backward")
    x = jax.ShapeDtypeStruct((2, 1024, 12, 64), dtype,
                             sharding=described_chip)
    with jax.default_matmul_precision("highest"):
        jaxpr = str(jax.make_jaxpr(fn)(x, x, x))
        text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert ("Precision.HIGHEST" in jaxpr) == (dtype == jnp.float32)
    # ... and only there: at jax's default setting nothing asks for it.
    assert "Precision.HIGHEST" not in str(jax.make_jaxpr(fn)(x, x, x))
    assert sorted(_flash_custom_calls(text)) == sorted(kernels)


_GROUPED_PRODUCTS = ["gate_up", "down", "row_gradient", "tgmm", "token_sums"]


def _grouped_product(product, m, d, f, groups, stacked, dtype, chip):
    """(function, operands' shapes on ``chip``, kind) of one of the expert
    layer's grouped matmuls: ``m`` rows, widths ``d`` and ``f``, ``groups``
    a layer inside a stack of ``stacked``; its plan counted under the
    budget."""
    from horovod_tpu.ops import grouped_matmul as gm

    kind, k, n = {"gate_up": ("gmm", d, f), "down": ("gmm", f, d),
                  "row_gradient": ("gmm_t", f, d), "tgmm": ("tgmm", d, f),
                  "token_sums": ("tgmm", 256, d)}[product]

    def spec(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if kind == "tgmm":
        groups = m // 512 if product == "token_sums" else groups
        fn, args = gm.tgmm, (spec(m, k), spec(m, n),
                             spec(groups, dtype=jnp.int32))
    else:
        transposed = kind == "gmm_t"
        fn = functools.partial(gm.gmm, transpose_rhs=transposed)
        args = (spec(m, k), spec(stacked, *((n, k) if transposed else (k, n))),
                spec(stacked, dtype=jnp.int32))
    plan = gm.kernel_plan(m, k, n, groups, dtype, kind)
    assert plan.vmem_bytes <= gm.VMEM_BUDGET
    return fn, args, kind


@pytest.mark.parametrize("product", _GROUPED_PRODUCTS)
@pytest.mark.parametrize("cell", ["olmoe", "zaya", "trinity", "glm"])
def test_grouped_matmul_kernels_compile_for_v5e(described_chip, cell,
                                                product):
    """``ops/grouped_matmul.py``'s kernels at the four expert cells'
    shapes, each within the scoped VMEM a Mosaic call has without asking
    (the calls set no limit, and the chip's compiler refuses a kernel
    that needs more): the contraction whole beside the columns its plan
    takes, ``tgmm``'s accumulator of a ``[1024, 1024]`` block, the sums
    over a token's rows at k 256."""
    from tools.pallas_bench import GMM_CELLS  # the four cells' shapes

    m, d, f, groups, layers = (GMM_CELLS[cell][key] for key in (
        "m", "d", "f", "groups", "layers"))
    fn, args, kind = _grouped_product(product, m, d, f, groups,
                                      layers * groups, jnp.bfloat16,
                                      described_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    name = "%tgmm." if kind == "tgmm" else "%gmm."
    assert [line for line in text.splitlines()
            if name in line and "tpu_custom_call" in line], text[-2000:]


@pytest.mark.parametrize("product", _GROUPED_PRODUCTS)
def test_grouped_matmul_kernels_compile_for_v5e_in_float32(described_chip,
                                                           product):
    """The gradient checks' float32 programs run the same kernels under
    ``jax.default_matmul_precision("highest")``, where Mosaic holds the
    bf16 parts of the rows it multiplies: the plans take narrower blocks
    and fewer rows at a time, within the same scoped VMEM."""
    fn, args, _ = _grouped_product(product, 16384, 2048, 1024, 16, 32,
                                   jnp.float32, described_chip)
    with jax.default_matmul_precision("highest"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("which", ["state", "grads"])
def test_ring_block_kernels_compile_for_v5e(described_chip, monkeypatch,
                                            which):
    """Ring attention's per-block kernels at the decoder's sp=4 shard."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    B, T, H, D = 8, 256, 12, 64
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16,
                             sharding=described_chip)
    if which == "state":
        def fn(q, k, v):
            return pa.flash_attention_block(q, k, v, q_off=0, k_off=0,
                                            causal=True)
        args = (x, x, x)
    else:
        def fn(q, k, v, do, lse, delta):
            return pa.flash_attention_block_grads(
                q, k, v, do, lse, delta, q_off=0, k_off=0, causal=True)
        stat = jax.ShapeDtypeStruct((B, H, T), jnp.float32,
                                    sharding=described_chip)
        args = (x, x, x, x, stat, stat)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_eva_attention_compiles_for_v5e(described_chip, monkeypatch, which):
    """``evabyte-t32768``'s mixer at its own size (32 heads of 128, 32,768
    bytes, windows of 2,048, chunks of 16): the exact set's kernels and
    the summaries' under the block-causal rule, whose mask divides integer
    vectors, compile to Mosaic, two calls a pass, and no score array of
    either set is in the program. Both calls read the head-major operands
    where they lie: nothing of q's size is copied into or out of the
    window-major order ``[16, 32, 2048, 128]`` (before the windows were
    cut from the merged heads: q, k, v and the float32 accumulator
    forward, those, dO, dQ, dK and dV backward), and the row statistics
    are written as ``[., ., 1]`` columns, 512 MiB each as they lie, three
    times where it was six: ``lse`` once for both calls, ``delta`` once
    for each (the compiler makes the windows' form from the rows by a
    reshape of its own)."""
    import re

    from horovod_tpu.ops.eva_attention import eva_attention

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    T, H, D, W, C = 32768, 32, 128, 2048, 16
    x, s = (jax.ShapeDtypeStruct((1, t, H, D), jnp.bfloat16,
                                 sharding=described_chip)
            for t in (T, T // C))

    def attend(q, k, v, k_sum, v_sum):
        return eva_attention(q, k, v, k_sum, v_sum, W, C)

    fn = attend if which == "forward" else jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=range(5))
    text = jax.jit(fn).lower(x, x, x, s, s).compile().as_text()
    calls = _flash_custom_calls(text)
    assert {k: len(v) for k, v in calls.items()} == (
        {"flash_fwd": 2} if which == "forward"
        else {"flash_fwd": 2, "flash_bwd": 2})
    for dims in (f"{T},{T}]", f"{T},{T // C}]", f"{W},{W}]"):
        assert dims not in text, dims
    n = T // W
    made = re.findall(  # (name, type and dims, opcode) of every instruction
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
        text[text.index("\nENTRY"):], re.M)
    window_major = [m for m in made if m[1].endswith((
        f"[{n},{H},{W},{D}]", f"[{n},{W},{H},{D}]"))
        and (m[2] == "copy" or "copy" in m[0])]
    assert not window_major, window_major
    columns = [m for m in made if m[1] in (f"f32[{H},{T},1]",
                                           f"f32[{H * n},{W},1]")
               and m[2] in ("copy", "reshape", "fusion", "transpose")]
    assert len(columns) <= (0 if which == "forward" else 3), columns


def _flash_custom_calls(text):
    """{kernel: [(result types, operand types)]} of a compiled program's
    Mosaic flash kernels, each type as (element type, dims) text."""
    from hlo_text import _ARRAY

    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        kernel = next((k for k in ("flash_fwd", "flash_bwd", "flash_dq",
                                   "flash_dkv") if k in name), None)
        if kernel:
            calls.setdefault(kernel, []).append((
                _ARRAY.findall(rest.split(" custom-call(")[0]),
                _ARRAY.findall(rest.split("operand_layout_constraints={")[1]
                               .split("}}")[0])))
    return calls


@pytest.mark.parametrize("window", [2048, None], ids=["window", "none"])
def test_grouped_kv_reach_the_kernels_at_their_head_count_on_v5e(
        described_chip, monkeypatch, window):
    """Value and gradient of ``flash_attention`` at Trinity-Mini's widths
    (32 query heads over 4 K/V heads of 128, two sequences of 8,192,
    bf16), compiled for the described chip: the forward and the fused
    backward, which Mosaic compiles at the VMEM its plan counts; both
    take K and V as ``bf16[8,8192,128]``, the backward returns dK and dV
    so, and the program holds no repeat of them to 32 heads and no sum
    over a group of 8."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                             sharding=described_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16,
                              sharding=described_chip)

    def loss(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, window=window
                                  ).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = _flash_custom_calls(text)
    assert sorted(calls) == ["flash_bwd", "flash_fwd"]
    grouped, merged = ("bf16", "8,8192,128"), ("bf16", "64,8192,128")
    for kernel, found in calls.items():
        for results, operands in found:
            # offsets, q, k, v, ...
            assert operands[1] == merged, (kernel, operands)
            assert operands[2] == operands[3] == grouped, (kernel, operands)
            if kernel == "flash_bwd":
                assert results == [merged, grouped, grouped], results
    # jnp.repeat's broadcast, in the [B, T, heads, D] layout or merged.
    for repeated in ("[2,8192,4,8,128]", "[8,8,8192,128]",
                     "[2,4,8,8192,128]"):
        assert repeated not in text, repeated
    # What is left at 32 heads is the Q side: q, o, dO, dQ.
    assert "bf16[2,8192,4,128]" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32-highest"])
def test_expert_layer_compiles_for_v5e(described_chip, monkeypatch, dtype):
    """``parallel/moe.py``'s layer and its gradient at OLMoE's widths (64
    experts of 2048 x 1024, 8 a token, 8,192 tokens): the Pallas grouped
    matmul's tiles fit the chip's fast memory in bf16 as benchmarked and
    in float32 at ``highest`` as the gradient check runs it; nine Mosaic
    calls (three matmuls forward, each with its two gradients)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel import moe

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    d, f, E, k = 2048, 1024, 64, 8
    mesh = Mesh(np.array([next(iter(described_chip.device_set))]), ("dp",))
    shapes = {"router": ((d, E), jnp.float32), "wg": ((E, d, f), dtype),
              "wu": ((E, d, f), dtype), "wd": ((E, f, d), dtype)}
    params = {name: jax.ShapeDtypeStruct(shape, dt, sharding=described_chip)
              for name, (shape, dt) in shapes.items()}
    x = jax.ShapeDtypeStruct((2, 4096, d), dtype, sharding=described_chip)

    def loss(x, p):
        y, stats = moe.moe_layer(x, p, E, axis_name="dp", top_k=k)
        return (jnp.sum(jnp.square(y.astype(jnp.float32))) + stats["lb"]
                + stats["z"])

    fn = jax.jit(jax.grad(jax.shard_map(
        loss, mesh=mesh, in_specs=(P(), {n: P() for n in shapes}),
        out_specs=P(), check_vma=False), argnums=(0, 1)))
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        text = fn.lower(x, params).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 9
    # Dropless and sparse: no [tokens x experts x capacity] array, no
    # one-hot contraction over the experts.
    assert "65536,64," not in text


def test_a_held_share_of_the_experts_compiles_for_v5e_by_windows(
        described_chip, monkeypatch):
    """``parallel/moe.py``'s layer and its gradient at Trinity-Mini's
    share (16 of 128 experts of 2048 x 1024 held, 8 a token, 16,384
    tokens, bf16): the section works on windows of 32,768 of the 131,072
    sorted assignments, in a loop whose trip count is data, forward and
    backward, and no array of all the assignments' rows is made anywhere;
    nine grouped matmuls and three sums over a token's rows a window."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel import moe

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    d, f, E, held, k = 2048, 1024, 128, 16, 8
    assert moe._window_rows(k * 16384, held, E) == 32768
    mesh = Mesh(np.array([next(iter(described_chip.device_set))]), ("dp",))
    shapes = {"router": ((d, E), jnp.float32),
              "wg": ((held, d, f), jnp.bfloat16),
              "wu": ((held, d, f), jnp.bfloat16),
              "wd": ((held, f, d), jnp.bfloat16)}
    params = {name: jax.ShapeDtypeStruct(shape, dt, sharding=described_chip)
              for name, (shape, dt) in shapes.items()}
    x = jax.ShapeDtypeStruct((2, 8192, d), jnp.bfloat16,
                             sharding=described_chip)

    def loss(x, p):
        y, stats = moe.moe_layer(x, p, E, 0, axis_name="dp", top_k=k,
                                 score_func="sigmoid")
        return jnp.sum(jnp.square(y.astype(jnp.float32))), stats["windows"]

    fn = jax.jit(jax.grad(jax.shard_map(
        loss, mesh=mesh, in_specs=(P(), {n: P() for n in shapes}),
        out_specs=(P(), P()), check_vma=False), argnums=(0, 1),
        has_aux=True))
    text = fn.lower(x, params).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 12
    assert text.count(" while(") >= 2
    assert "[131072,2048]" not in text and "[16384,8,2048]" not in text
    assert "[32768,2048]" in text


def _expert_operands(text):
    """Of a compiled program's Mosaic grouped matmuls (``%gmm.<n>``, whose
    last operand is the experts' matrices): the instruction that makes
    that operand, by kernel."""
    made = {}
    for line in text.splitlines():
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        made[name] = rest
    return {name: made[rest.split("custom-call(")[1].split(")")[0]
                       .split(", ")[-1].split("*/")[-1]]
            for name, rest in made.items()
            if name.startswith("%gmm.") and "custom-call(" in rest}


def test_layer_scan_copies_no_expert_matrix_for_the_kernels_on_v5e(
        described_chip, monkeypatch):
    """The decoder's gradient over a scan of two expert layers: every
    grouped matmul that needs a layer's matrices takes the stage's whole
    ``[L * E, ...]`` stack, a bitcast of the parameter, and none a
    ``dynamic-slice`` of it that XLA would first have to copy out (the
    Mosaic call's operand is a buffer of its own); handed the slices, as
    before, all six take such a copy."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import moe
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    cfg = tr.TransformerConfig(
        vocab=512, d_model=256, n_heads=2, d_head=128, n_layers=2,
        max_seq=512, use_moe=True, n_experts=8, d_expert=128, moe_top_k=2,
        norm="rmsnorm", qk_norm=True, rope=True, dtype=jnp.bfloat16)
    mesh = build_parallel_mesh(list(described_chip.device_set), sp=1, tp=1,
                               pp=1)
    specs = tr._param_specs(cfg)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=NamedSharding(mesh, specs[k]))
              for k, v in jax.eval_shape(
                  lambda: tr.init_params(cfg, jax.random.PRNGKey(0), 1)
              ).items()}
    tokens = jax.ShapeDtypeStruct((2, 512), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", "sp")))

    def operands():
        grad = jax.jit(jax.grad(tr.make_loss_fn(cfg, mesh, 1)))
        made = _expert_operands(
            grad.lower(params, tokens, tokens).compile().as_text())
        assert len(made) == 6, made  # three forward, three row gradients
        # "bf16[16,256,128]{layout} bitcast(...": shape and opcode.
        return [" ".join(re.match(r"(\w+\[[\d,]*\])\S* ([\w\-]+)\(",
                                  rest).groups())
                for rest in made.values()]

    in_place = operands()
    # ... as it lies: the parameter's bitcast, or the loop's own operand.
    assert {made.split()[0] for made in in_place} == {
        "bf16[16,256,128]", "bf16[16,128,256]"}, in_place
    assert {made.split()[1] for made in in_place} <= {
        "bitcast", "get-tuple-element"}, in_place
    slice_taking = moe._grouped_matmul
    monkeypatch.setattr(
        moe, "_grouped_matmul", lambda lhs, rhs, sizes, stack=None, layer=0:
        slice_taking(lhs, rhs, sizes))
    copied = operands()
    assert set(copied) == {"bf16[8,256,128] fusion",
                           "bf16[8,128,256] fusion"}, copied


def test_expert_kernels_take_a_stack_of_the_published_depth_on_v5e(
        described_chip, monkeypatch):
    """OLMoE's sixteen layers in one stage: the grouped matmul and its two
    gradients over a ``[16, 64, 2048, 1024]`` stack, 1,024 groups of which
    64 have rows; the kernels' tables of 128 + 1,024 - 1 entries fit."""
    from horovod_tpu.parallel import moe

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    rows = jax.ShapeDtypeStruct((65536, 2048), jnp.bfloat16,
                                sharding=described_chip)
    stack = jax.ShapeDtypeStruct((16, 64, 2048, 1024), jnp.bfloat16,
                                 sharding=described_chip)
    sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=described_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=described_chip)

    def product(rows, stack, sizes, layer):
        def of(rows, rhs):
            return jnp.sum(moe._grouped_matmul(
                rows, rhs, sizes, jax.lax.stop_gradient(stack),
                layer).astype(jnp.float32))
        return jax.value_and_grad(of, (0, 1))(rows, stack[layer])

    text = jax.jit(product).lower(rows, stack, sizes, layer).compile(
        ).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "s32[1151]" in text
    assert all(made.startswith("bf16[1024,2048,1024]") and " bitcast(" in made
               for made in _expert_operands(text).values())


def _materialised(text):
    """(result shape, opcode) of every instruction of a compiled program
    that writes its result to memory: those of the entry, the loops'
    bodies and the like, not those inside a fusion."""
    fused = set(re.findall(r"fusion\([^\n]*calls=(%[\w.\-]+)", text))
    found = []
    for computation in re.split(
            r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text):
        name = re.match(r"(?:ENTRY )?(%[\w.\-]+)", computation)
        if not name or name.group(1) in fused:
            continue
        found += re.findall(
            r"\n\s+(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
            computation)
    return found


def _scan_gradient_text(described_chip, heads, T=8192, P=64, N=128, Q=256):
    """The compiled forward and gradient of ``ops/ssd.py``'s scan at
    granite-h-t8192's shape (one sequence of 8,192 tokens, heads of 64,
    state 128, chunk 256, bf16)."""
    from horovod_tpu.ops import ssd

    def operand(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=described_chip)

    args = (operand((1, T, heads, P), jnp.bfloat16),
            operand((1, T, heads), jnp.float32),
            operand((heads,), jnp.float32),
            operand((1, T, N), jnp.bfloat16), operand((1, T, N), jnp.bfloat16),
            operand((heads,), jnp.float32))

    def loss(*a):
        return ssd.ssd_chunked(*a, chunk=Q).astype(jnp.float32).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(
        *args).compile().as_text()


def _tiles_and_wide_copies(text, heads, T=8192, P=64, Q=256):
    """Of what a compiled scan writes to memory: the [.., heads, Q, Q]
    decay and score arrays, and float32 arrays of ``x``'s shape."""
    written = _materialised(text)
    assert len(written) > 20  # the rule reads this compiler's text
    wide = {f"f32[1,{T},{heads},{P}]", f"f32[1,{heads * P},{T}]",
            f"f32[1,{T},{heads * P}]", f"f32[1,{T // Q},{Q},{heads},{P}]",
            f"f32[1,{T // Q},{heads},{P},{Q}]"}
    return ([w for w in written if w[0].endswith(f"{heads},{Q},{Q}]")],
            [w for w in written if w[0] in wide])


@pytest.mark.parametrize("heads", [64, 32], ids=["the-cell", "a-tp2-member"])
def test_scan_kernels_compile_for_v5e(described_chip, monkeypatch, heads):
    """The kernel pair at the cell's shape and with the 32 heads of a
    ``tp`` 2 member: both Mosaic calls by name, and neither the decay and
    score arrays nor a float32 copy of ``x`` written to memory."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    text = _scan_gradient_text(described_chip, heads)
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert len(calls) == 2
    assert any("ssd_fwd" in c for c in calls)
    assert any("ssd_bwd" in c for c in calls)
    assert _tiles_and_wide_copies(text, heads) == ([], [])


def test_the_einsum_form_writes_what_the_scan_kernels_keep_in_vmem(
        described_chip, monkeypatch):
    """The fall-back at the same shape: no Mosaic call, and the rule above
    finds the decay and score arrays and the widened ``x`` in memory."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (False, False))
    text = _scan_gradient_text(described_chip, 64)
    assert "tpu_custom_call" not in text
    tiles, wide = _tiles_and_wide_copies(text, 64)
    assert tiles and wide


# ---- the gradient exchange on the four chips of a described host -----------

class _ConvNet(nn.Module):
    """Ten 3x3 convolutions of 256 channels under batch-norm: 23.6 MB of
    float32 kernels."""

    @nn.compact
    def __call__(self, x, train=False):
        x = nn.Conv(256, (3, 3), dtype=jnp.bfloat16)(x)
        for _ in range(10):
            x = nn.Conv(256, (3, 3), dtype=jnp.bfloat16)(x)
            x = nn.BatchNorm(use_running_average=not train,
                             dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
        return nn.Dense(10, dtype=jnp.float32)(jnp.mean(x, axis=(1, 2)))


class _MLP8(nn.Module):
    """tests/test_fusion_overlap.py's model: 16 float32 leaves."""

    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        for f in (32,) * 7 + (10,):
            x = nn.Dense(f)(x)
            if f != 10:
                x = jax.nn.relu(x)
        return x


EXCHANGES = {
    # model, sample shape, bucket_cap_bytes
    "conv-bn-auto": (_ConvNet, (8, 32, 32, 3), "auto"),
    "conv-bn-cap6MiB": (_ConvNet, (8, 32, 32, 3), 6 << 20),
    "mlp8-cap8192": (_MLP8, (16, 16), 8192),
}


def _entry_schedule(text):
    """[(name, opcode, line)] of the entry computation, in the order the
    compiler scheduled it."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    found = []
    for line in lines[start + 1:]:
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][a-z\-]*)\(",
                     line)
        if m:
            found.append((m.group(1), m.group(2), line))
    return found


def _dp_step_text(model, shape, mesh, cap):
    """(abstract parameters, the scheduled entry computation) of
    ``make_train_step`` compiled for the described ``mesh``, through
    ``.lower(...).compile()`` as the benchmark compiles it."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.training import init_train_state, make_train_step

    opt = optax.sgd(0.1, momentum=0.9)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(AXIS_GLOBAL))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=replicated),
        jax.eval_shape(lambda k: init_train_state(
            model, opt, k, jnp.zeros((1,) + shape[1:], jnp.float32)),
            jax.random.PRNGKey(0)))
    images = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharded)
    labels = jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=sharded)
    step = make_train_step(model, opt, mesh, bucket_cap_bytes=cap)
    return state.params, _entry_schedule(
        step.lower(state, images, labels).compile().as_text())


@pytest.mark.parametrize("case", sorted(EXCHANGES))
def test_the_exchange_moves_the_leaves_on_v5e(described_host, monkeypatch,
                                              case):
    """``make_train_step`` compiled for four described chips, through
    ``.lower(...).compile()`` (the benchmark's way): every all-reduce is
    over leaves where they lie (no operand of the model's size, no
    ``concatenate`` under ``exchange``). With no cap it is one tuple
    all-reduce; with one the option reaches the executable: the gradients
    are reduced in at least as many all-reduce instructions as the
    planner would cut buckets at that cap, and the first is scheduled
    before the last weight gradient."""
    import math

    from horovod_tpu.common import fusion

    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
    model_cls, shape, cap = EXCHANGES[case]
    params, schedule = _dp_step_text(model_cls(), shape, described_host, cap)
    leaves = jax.tree_util.tree_leaves(params)
    reduces = [(i, line) for i, (_, opcode, line) in enumerate(schedule)
               if opcode in ("all-reduce", "all-reduce-start")]
    total = sum(math.prod(l.shape) for l in leaves)
    for _, line in reduces:
        result = line.split(" all-reduce")[0]
        assert all(math.prod(int(d) for d in dims.split(",") if d) < total
                   for dims in re.findall(r"\[([0-9,]*)\]", result))
    assert not [line for _, opcode, line in schedule
                if opcode == "concatenate" and "exchange" in line]
    if cap == "auto":
        assert len(reduces) == 1
        return
    planned = fusion.plan_buckets_for(leaves, cap)
    assert len(planned) > 2
    assert len(reduces) >= len(planned), (len(reduces), len(planned))
    weight_gradients = [i for i, (_, opcode, line) in enumerate(schedule)
                        if opcode == "fusion"
                        and "transpose(jvp(forward))" in line]
    assert reduces[0][0] < weight_gradients[-1]


def test_one_participant_compiles_with_no_all_reduce(described_chip):
    """``resnet50-1chip``'s case: the program of a mesh of one chip holds
    no all-reduce, cap or none."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array([next(iter(described_chip.device_set))]),
                (AXIS_GLOBAL,))
    for cap in ("auto", 8192):
        _, schedule = _dp_step_text(_MLP8(), (16, 16), mesh, cap)
        assert not [line for _, opcode, line in schedule
                    if opcode.startswith("all-reduce")]


# ---- the KDA scan's kernels and the latent mixer's two widths ---------------

@pytest.mark.parametrize("chunk, dtype, heads", [
    (64, "bfloat16", 4), (128, "bfloat16", 4), (256, "bfloat16", 2),
    (128, "float32", 4)])
def test_kda_kernels_compile_for_v5e(described_chip, monkeypatch, chunk,
                                     dtype, heads):
    """``ops/kda.py``'s kernel pair at the cell's shape (one sequence of
    16,384 tokens, 32 heads of 128) at the chunks the plan takes, and in
    float32 at ``highest`` as ``grad_check_ling`` runs them (where a
    step's heads hold most VMEM): both Mosaic calls by name inside what
    the plan counted, ``heads`` heads a step of the backward call, and the
    states a chunk starts from the forward call's second result."""
    from horovod_tpu.ops import kda

    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))
    T, H, K = 16384, 32, 128
    wide = jax.ShapeDtypeStruct((1, T, H, K), jnp.dtype(dtype),
                                sharding=described_chip)
    gate = jax.ShapeDtypeStruct((1, T, H, K), jnp.float32,
                                sharding=described_chip)
    beta = jax.ShapeDtypeStruct((1, T, H), jnp.float32,
                                sharding=described_chip)
    assert kda.kernel_plan(H, K, K, chunk, wide.dtype).heads == heads
    fn = jax.grad(lambda *a: kda.kda_chunked(*a, chunk=chunk).astype(
        jnp.float32).sum(), argnums=range(5))
    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        text = jax.jit(fn).lower(wide, wide, wide, gate,
                                 beta).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert len(calls) == 2
    assert any("kda_fwd" in c for c in calls)
    assert any("kda_bwd" in c for c in calls)
    assert f"f32[1,{H},{T // chunk},{K},{K}]" in text


def test_the_latent_mixers_two_widths_compile_for_v5e(described_chip,
                                                      monkeypatch):
    """Queries and keys of 192 channels, values of 128 riding zeros up to
    192, at the cell's 32 heads and 16,384 tokens: the forward and the
    fused backward kernel at the one width they are given, and the
    result's first 128 columns."""
    monkeypatch.setattr(pa, "_resolve_dispatch", lambda up: (True, False))

    def attend(q, k, v):
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, q.shape[-1] - v.shape[-1])])
        return pa.flash_attention(q, k, v, causal=True)[..., :128]

    fn = jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))
    qk = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16,
                              sharding=described_chip)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=described_chip)
    calls = _flash_custom_calls(
        jax.jit(fn).lower(qk, qk, v).compile().as_text())
    assert sorted(calls) == ["flash_bwd", "flash_fwd"]
    (results, operands), = calls["flash_fwd"]
    assert all(dims.endswith("16384,192") for _, dims in operands[1:])
