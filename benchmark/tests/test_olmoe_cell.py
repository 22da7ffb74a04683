"""The cell ``olmoe-t4096`` on the CPU: rehearsed at a tiny size traced
and untraced (``benchmark/conftest.py`` gives ``tiny`` its sizes, so
``test_harness.py`` rehearses it too), ``flops_moe`` against counts by
hand, the six new readers on a hand-made trace, on two steps recorded on
the chip (tests/data/recorded_olmoe.json.gz, cut from PR 26's traced
run) and on a ``gpt2s`` trace, and the gradient check at a tiny size."""
import gzip
import importlib
import json
import os
import time

import pytest

from benchmark import flops_moe, harness
from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped, _sum_ms
from benchmark.tests.test_trace_reduce import DATA, _load

NEW = ("moe_ms", "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
       "attn_block_ms", "moe_load_max_over_mean")
NS = 1e-6  # ms


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_olmoe")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    with open(os.path.join(tiny_root, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        assert json.load(f)["hidden_size"] == 64  # the tiny copy, not 2048
    result = harness.run_cell("olmoe-t4096", seed=3000000019, seconds=0.2,
                              trace=trace, t_start=time.perf_counter(),
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    out = capsys.readouterr().out
    # 2 x 32 tokens, 3 of 8 experts each, counted before the window.
    assert "every layer's sum is 3 x 64" in out
    assert "assignments the float32 reference routes elsewhere: 0 of 384" \
        in out
    if trace:
        # No device plane on the CPU: of the new metrics only the
        # runner's own count is there.
        assert set(result["metrics"]) == {"host_dispatch_ms",
                                          "moe_load_max_over_mean"}
        load = result["metrics"]["moe_load_max_over_mean"]
        assert load["unit"] == "x" and 1.0 <= load["value"] <= 8.0
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_entries_are_the_issues():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "olmoe-1b-7b"
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name="olmoe-t4096", config="olmoe-1b-7b",
        traffic="t4096-b2", chips=1)
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    for metric in bench["per_layer"][-6:]:
        assert metric["workloads"] == ["olmoe-t4096"]
        assert metric["moves"] == "samples_per_s_chip"


def test_the_configuration_holds_every_published_key():
    """The catalog row of OLMoE-1B-7B-0125-Instruct, key for key; only
    the depth is cut."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 2
    for key in ("router_aux_loss_coef", "router_z_loss_coef", "dtype",
                "deployment"):
        assert key in config["assumed"]


# ---- counts by hand ---------------------------------------------------------

def test_model_flops_by_hand():
    # OLMoE at two layers, T 4096. A layer: attention 4 x 2048^2 =
    # 16,777,216; router 2048 x 64 = 131,072; 8 experts x 3 x 2048 x 1024
    # = 50,331,648: 67,239,936. Head 2048 x 50,304 = 103,022,592.
    assert flops_moe.decoder_moe_matmul_params(
        2048, 2, 1024, 8, 64, 50304) == 2 * 67_239_936 + 103_022_592
    per_token = flops_moe.decoder_moe_train_flops_per_token(
        2048, 2, 1024, 8, 64, 50304, 4096)
    assert per_token == 6 * 237_502_464 + 6 * 2 * 4096 * 2048
    assert round(per_token / 1e6) == 1526  # the issue's 1,526 MFLOP
    # The full 16 layers: the head is 8 % of it, 40 % here.
    full = flops_moe.decoder_moe_train_flops_per_token(
        2048, 16, 1024, 8, 64, 50304, 4096)
    assert round(100 * 6 * 103_022_592 / full) == 8
    assert round(100 * 6 * 103_022_592 / per_token) == 41


def test_grouped_matmul_operations_and_bytes_by_hand():
    # A layer of the cell: 8 x 8,192 = 65,536 rows.
    assert flops_moe.grouped_matmul_train_flops(8192, 8, 2048, 1024) == \
        18 * 65536 * 2048 * 1024
    # Tiny: 2 tokens, 1 expert each, d 4, f 8, 2 experts, bf16. Weights
    # 3 x 2 x 4 x 8 x 2 B = 384 B, four times; rows 2 x 4 x 2 B = 16 B,
    # five times.
    assert flops_moe.grouped_matmul_train_flops(2, 1, 4, 8) == 1152
    assert flops_moe.grouped_matmul_train_bytes(2, 1, 4, 8, 2, 2) == \
        4 * 384 + 5 * 16
    # At the cell's shapes on a v5e compute bounds it: 12.56 ms of
    # operations against 5.57 ms of bytes a layer.
    ops_ms = 1e3 * 18 * 65536 * 2048 * 1024 / 197e12
    bytes_ms = 1e3 * flops_moe.grouped_matmul_train_bytes(
        8192, 8, 2048, 1024, 64, 2) / 819e9
    assert round(ops_ms, 2) == 12.56 and round(bytes_ms, 2) == 5.57


# ---- the readers by hand ----------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step                   forward, attention
#   %closed_call.1 (a Mosaic call) 200 ns a step      backward, a grouped
#                                                     matmul under moe_gmm
#   %all-reduce.1  250 ns, then 300 ns                backward, moe_dispatch
#   %fusion.2   100 ns a step, beside %all-reduce.1   forward, moe_route
#   %fusion.3   100 ns a step                         forward, the moe
#                                                     block's own norm
LAYER = "while/body/closed_call/while/body/closed_call"
HAND_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/attention/btd,dchk->btchk"
                 "/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                      "moe_experts/moe_gmm/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                     "moe_dispatch/gather",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_route/top_k",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/moe/checkpoint/rsqrt",
}


class _MoeJob:
    model_flops_per_step = 0.0
    moe_load_max_over_mean = 1.5
    moe = dict(tokens=2, experts_per_token=1, d=4, d_expert=8, n_experts=2,
               layers=1, itemsize=2)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, want", [
    # the kernel, the gather with the route fusion inside it, the norm
    ("moe_ms", (200 + 275 + 100) * NS),
    ("moe_route_ms", 275 * NS),  # a union: %fusion.2 overlaps the gather
    ("moe_experts_ms", 200 * NS),
    # 1,616 B over 1e12 B/s bounds it (1,152 FLOPs over 1e12 FLOP/s is
    # less): 1.616 ns a step of 200
    ("moe_experts_roofline", 100 * 1.616 / 200),
    ("attn_block_ms", 290 * NS),
    ("moe_load_max_over_mean", 1.5)])
def test_each_new_reader_by_hand(small, metric, want):
    ctx = _scoped(small, HAND_PATHS)
    ctx.job = _MoeJob()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    assert _read(metric, ctx) == pytest.approx(want)


@pytest.mark.parametrize("paths", [
    {},  # the parent's trace, or the CPU's: no path at all
    {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    ctx = _scoped(small, paths)
    for metric in NEW:
        assert _read(metric, ctx) is None


def test_a_dense_decoders_trace_has_no_expert_layer():
    """Two steps of ``gpt2s-t128`` recorded on the chip: the expert
    layer's readers find nothing; ``attn_block_ms`` reads the attention
    block every decoder has (BENCHMARK.json reports it in the new cell
    alone)."""
    with gzip.open(os.path.join(DATA, "recorded_scopes.json.gz"), "rt") as f:
        data = json.load(f)["gpt2s-t128"]
    trace = {plane: {line: [tuple(e) for e in events]
                     for line, events in lines.items()}
             for plane, lines in data["trace"].items()}
    ctx = _scoped(trace, data["paths"])
    for metric in NEW:
        if metric != "attn_block_ms":
            assert _read(metric, ctx) is None
    assert _read("attn_block_ms", ctx) == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/attention/" in p))


# ---- recorded on the chip ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "recorded_olmoe.json.gz"), "rt") as f:
        data = json.load(f)["olmoe-t4096"]
    trace = {plane: {line: [tuple(e) for e in events]
                     for line, events in lines.items()}
             for plane, lines in data["trace"].items()}
    return _scoped(trace, data["paths"])


def test_recorded_expert_layer_splits_into_its_scopes(recorded):
    ctx = recorded
    assert len(tr.step_events(ctx.lines)) == 2
    moe, route, experts = (_read(m, ctx) for m in
                           ("moe_ms", "moe_route_ms", "moe_experts_ms"))
    assert moe == pytest.approx(_sum_ms(ctx, lambda n, p: "/moe/" in p))
    assert experts == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/moe_experts/" in p + "/"))
    # The two parts are disjoint and all of the block but its own norm
    # and residual add.
    assert 0.95 * moe <= route + experts <= moe
    # The grouped matmuls are Mosaic calls under moe_gmm: per step and
    # layer three forward, and backward three by the rows (gmm) and
    # three by the weights (tgmm).
    kernels = [e for e in ctx.scoped_events
               if "moe_gmm" in sr.segments(e[3])
               and tr.is_mosaic_kernel(e[0])]
    assert len(kernels) == 2 * 2 * 9
    assert sr.kernel_ms(ctx, "moe_gmm") == pytest.approx(
        sum(e[2] for e in kernels) / 2 / 1e6)
    # The rest of moe_experts: the gated product, the transposes the
    # weight-gradient kernel wants, the groups' tile tables.
    assert 0.8 * experts < sr.kernel_ms(ctx, "moe_gmm") < experts
    # The flash kernels at D 128 sit in the attention block.
    attention = _read("attn_block_ms", ctx)
    flash = sum(sr.kernel_ms(ctx, k)
                for k in ("flash_fwd", "flash_dq", "flash_dkv"))
    assert 0.3 * attention < flash < attention
    assert sr.scope_ms(ctx, "mlp") is None


# ---- the gradient check, at a tiny size -------------------------------------

def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_moe

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_moe.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    assert "float32 router" in out and "bf16    wg" in out
    assert json.loads(out.splitlines()[-1])["ok"] is True
