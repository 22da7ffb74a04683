"""The cell ``zaya1-8b-t16384`` on the CPU: rehearsed at a tiny size traced
and untraced through ``harness.load_cell`` and the runner, ``flops_zaya``
against counts by hand, the thirteen new readers on a hand-made trace and
``None`` where there is nothing to read, the new entries held by name, the
gradient and the limit check at a tiny size, and the proof that no file
under ``benchmark/`` that the parent had was changed.

The cell's tiny sizes are registered here, as this module is imported
(``benchmark/conftest.py`` and ``tests/tiny.py`` are not this PR's to
edit): ``test_harness.py`` rehearses every cell of BENCHMARK.json, so run
it with this file collected (``pytest benchmark/tests``), never alone."""
import importlib
import json
import os
import subprocess
import time

import pytest

from benchmark import flops_zaya, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

tiny.TINY_CONFIGS.setdefault("zaya1-8b", dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, num_experts_published=8,
    num_experts=4, experts_held=[0, 4], router_hidden_size=16,
    vocab_size=256, max_position_embeddings=64, num_hidden_layers=3,
    head_block_tokens=24, dtype="float32",
    # 64 tokens: the rate's rise cut short, so that the loss falls by more
    # than one token routed elsewhere moves it.
    optimizer=dict(name="adamw", learning_rate=3e-4, warmup_steps=4)))
tiny.TINY_TRAFFIC.setdefault("t16384-b1", dict(batch_per_chip=2, seq_len=32))

CELL = "zaya1-8b-t16384"
NEW = ("cca_attn_ms", "cca_proj_ms", "cca_conv_ms", "cca_flash_ms",
       "cca_flash_roofline", "zaya_router_ms", "zaya_moe_ms",
       "zaya_moe_experts_ms", "zaya_moe_experts_roofline",
       "zaya_moe_held_rows_share", "zaya_head_loss_ms",
       "zaya_head_roofline", "zaya_scan_ms")
HOST = ("zaya_moe_held_rows_share",)
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
ZAYA = dict(d=2048, n_heads=8, n_kv_heads=2, head_dim=128, time0=2, time1=2,
            router_hidden=256, n_experts=16, d_expert=2048, n_layers=10,
            vocab_rows=131136, seq_len=16384)


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _published():
    """The catalog's row (model-configs, architectures.jsonl, ZAYA1-8B),
    where the guide is installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not installed here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "ZAYA1-8B")


def _config():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "zaya1-8b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_zaya")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    """Build, lower, reference check, warm-up and a window through the
    harness: every comparison of the runner is printed with its tolerance
    and passes."""
    spec = harness.load_cell(CELL, tiny_root)
    assert spec["config"]["hidden_size"] == 64  # the tiny copy
    assert spec["config"]["runner"] == "decoder_zaya"
    result = harness.run_cell(CELL, seed=3000000019, seconds=0.2,
                              trace=trace, t_start=time.perf_counter(),
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    said = capsys.readouterr().out
    for what in ("first-step loss vs float32 reference",
                 "every token's cross-entropy of the first step vs float32 "
                 "reference",
                 "the same, the median of the absolute difference",
                 "sum to the tokens (one a token, nothing dropped)",
                 "assignments the float32 reference routes elsewhere",
                 "the bias after the first step vs the rule on the step's "
                 "own counts",
                 "tokens per expert, the first step's own counts"):
        assert what in said, what
    if trace:
        assert set(result["metrics"]) == {"host_dispatch_ms", *HOST}
        assert 0 < result["metrics"]["zaya_moe_held_rows_share"][
            "value"] < 1
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_entries_are_the_issues():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["zaya1-8b"]
    assert (config["file"], config["source"], config["reduced"]) == (
        "benchmark/configs/zaya1-8b.json",
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json",
        ["num_hidden_layers", "num_experts", "vocab_size"])
    cell = by_name["workloads"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1-8b", "t16384-b1", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for name in NEW:
        metric = by_name["per_layer"][name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "samples_per_s_chip"
        assert metric["source"] == ("host_clock" if name in HOST
                                    else "device_trace")
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert (reader.LAYER, reader.UNIT) == (metric["layer"],
                                               metric["unit"])
    spec = harness.load_cell(CELL, tiny.ROOT)
    assert {m["name"] for m in spec["per_layer"]} == set(NEW + SHARED)
    assert all(CELL not in m.get("workloads", []) or m["name"] in NEW
               for m in bench["per_layer"])
    assert spec["traffic"]["seq_len"] == 16384
    assert spec["traffic"]["batch_per_chip"] == 1


def test_the_configuration_holds_the_published_keys():
    """The catalog's row key for key; the depth, the experts held and the
    vocabulary are the chip's share, each with its published value beside
    it; no width is among them."""
    from benchmark.runners import decoder_zaya

    config, published = _config(), _published()
    assert config["source"] == published["source_url"]
    changed = {k for k, v in published["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in config["reduced"]:
        assert config[key + "_published"] == published["config"][key]
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 8]
    assert config["vocab_size"] * 2 == config["vocab_size_published"]
    assert "two chips share each layer" in config["deployment"]
    for key in ("residual_scales", "value_shift", "qk_mean", "qk_norm",
                "rotation_layout", "router_state", "router_mlp", "bias_rate",
                "bias_rule", "loss", "dtype", "optimizer", "initialisation",
                "layout", "recompute", "head_block", "bytes_per_parameter"):
        assert key in config["assumed"], key
    cfg = decoder_zaya.transformer_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head,
            cfg.d_expert, cfg.n_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.router_hidden, cfg.cca_time0, cfg.cca_time1, cfg.vocab) == (
        2048, 8, 2, 128, 2048, 16, 8, 1, 256, 2, 2, 131136)
    assert (cfg.partial_rotary_factor, cfg.rope_theta, cfg.norm_eps,
            cfg.expert_bias_rate) == (0.5, 5e6, 1e-5, 0.001)
    assert cfg.kinds == ("cca",) * config["num_hidden_layers"]
    assert cfg.tie_embeddings and cfg.residual_scales and not cfg.pos_table


def test_model_flops_by_hand():
    # W_q 2048 x 1024, W_k and W_v 2048 x 256, W_o 1024 x 2048; by head 2
    # taps x 10 heads x 128^2; depthwise 2 taps x 1,280 channels.
    mixer = flops_zaya.cca_matmul_params(2048, 8, 2, 128, 2, 2)
    assert mixer == (2_097_152 + 2 * 524_288 + 2_097_152 + 327_680
                     + 2_560) == 5_573_120
    router = flops_zaya.router_matmul_params(2048, 256, 16)
    assert router == 524_288 + 2 * 65_536 + 4_096 == 659_456
    expert = 3 * 2048 * 2048
    per_token = flops_zaya.zaya_train_flops_per_token(
        held_rows_per_token=0.5, **ZAYA)
    scores = 12 * 1024 * 16384 / 2
    head = 2048 * 131136
    assert per_token == pytest.approx(
        10 * (6 * mixer + scores + 6 * (router + 0.5 * expert)) + 6 * head)
    assert round(per_token / 1e6) == 3369
    # The mixers are 40 % of it (their kernels 30 %), the head 48 %, the
    # router and the held experts 12 %.
    assert round(100 * 10 * (6 * mixer + scores) / per_token) == 40
    assert round(100 * 10 * scores / per_token) == 30
    assert round(100 * 6 * head / per_token) == 48
    more = flops_zaya.zaya_train_flops_per_token(
        held_rows_per_token=1.0, **ZAYA)
    assert more - per_token == pytest.approx(10 * 6 * 0.5 * expert)


def test_kernel_operations_and_bytes_by_hand():
    # Tiny: B 1, Hq 4, Hkv 2, T 4, D 8: 7 x 4 x 16 x 8; six arrays of 4 and
    # six of 2 heads x 32 x 2 B.
    assert flops_zaya.cca_flash_train_flops(1, 4, 4, 8) == 3584
    assert flops_zaya.cca_flash_train_bytes(1, 4, 2, 4, 8, 2) == 2304
    ops_ms = 1e3 * flops_zaya.cca_flash_train_flops(1, 8, 16384,
                                                   128) / 197e12
    bytes_ms = 1e3 * flops_zaya.cca_flash_train_bytes(
        1, 8, 2, 16384, 128, 2) / 819e9
    assert round(ops_ms, 1) == 9.8 and round(bytes_ms, 1) == 0.3
    assert flops_zaya.head_train_flops(3, 4, 5) == 360
    head_ms = 1e3 * flops_zaya.head_train_flops(16384, 2048,
                                                131136) / 197e12
    assert round(head_ms) == 134


# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1 140 + 150 ns a step; %closed_call.1 (a Mosaic call) 200 ns;
#   %all-reduce.1 250 ns, then 300 ns; %fusion.2 100 ns, beside it;
#   %fusion.3 100 ns a step
LAYER = "while/body/closed_call/while/body/closed_call"
MIXER_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/cca/cca_kv/btd,dhk->bthk/"
                 "dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/cca/"
                      "flash_bwd/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/cca/cca_conv/"
                     "bthk,hkc->bthc/dot_general",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/cca/cca_norm/mul",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/cca/cca_out/"
                 "bthk,hkd->btd/dot_general",
}
REST_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/moe/zaya_router/"
                 "btd,dr->btr/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                      "moe_experts/moe_gmm/pallas_call",
    "%all-reduce.1": "jit(s)/transpose(jvp(forward))/head/while/body/"
                     "head_block/nv,nd->vd/dot_general",
    "%fusion.2": "jit(s)/jvp(forward)/while/body/dynamic_slice",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_route/reduce_max",
}


class _Job:
    model_flops_per_step = 0.0
    moe_held_rows_share = 0.5
    cca = dict(batch=1, heads=4, kv_heads=2, seq_len=4, head_dim=8,
               layers=1, itemsize=2)
    moe_share = dict(d=4, d_expert=8, experts_held=2, layers=1, itemsize=2,
                     rows_held=3.0)
    zaya_head = dict(tokens=3, d=4, vocab_rows=5)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, paths, want", [
    # the convolution with the norm's fusion inside it
    ("cca_attn_ms", MIXER_PATHS, (290 + 200 + 275 + 100) * NS),
    ("cca_proj_ms", MIXER_PATHS, (290 + 100) * NS),
    ("cca_conv_ms", MIXER_PATHS, 275 * NS),
    ("cca_flash_ms", MIXER_PATHS, 200 * NS),
    # 3,584 FLOPs over 1e12 FLOP/s bounds it (2,304 B over 1e12 B/s is
    # less): 3.584 ns a step of 200
    ("cca_flash_roofline", MIXER_PATHS, 100 * 3.584 / 200),
    ("zaya_moe_ms", MIXER_PATHS, None),
    ("zaya_head_loss_ms", MIXER_PATHS, None),
    ("zaya_router_ms", REST_PATHS, (290 + 100) * NS),
    ("zaya_moe_ms", REST_PATHS, (290 + 200 + 100) * NS),
    ("zaya_moe_experts_ms", REST_PATHS, 200 * NS),
    # 1,728 FLOPs over 1e12 bounds it (1,656 B is less): 1.728 ns of 200
    ("zaya_moe_experts_roofline", REST_PATHS, 100 * 1.728 / 200),
    ("zaya_moe_held_rows_share", REST_PATHS, 0.5),
    ("zaya_head_loss_ms", REST_PATHS, 275 * NS),
    # 360 FLOPs over 1e12: 0.36 ns of 275
    ("zaya_head_roofline", REST_PATHS, 100 * 0.36 / 275),
    ("zaya_scan_ms", REST_PATHS, 100 * NS),
    ("cca_attn_ms", REST_PATHS, None)])
def test_each_new_reader_by_hand(small, metric, paths, want):
    ctx = _scoped(small, paths)
    ctx.job = _Job()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    got = _read(metric, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("paths", [
    {}, {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    """Where the program has none of the scopes every reader returns None
    and does not raise: with this cell's job, and with a job that knows
    nothing of the cell."""
    for job in (_Job(), None):
        ctx = _scoped(small, paths)
        if job is not None:
            ctx.job = job
            ctx.job.moe_held_rows_share = None  # no first step was run
        ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
        for metric in NEW:
            assert _read(metric, ctx) is None, metric


def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_zaya

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_zaya.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    for leaf in ("c_conv0_q", "c_conv1_k", "c_beta", "r_gamma", "r_w2",
                 "res1", "wg", "embed"):
        assert f"float32 {leaf} " in out, leaf
    assert json.loads(out.splitlines()[-1])["ok"] is True


def test_limit_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    """In a float32 program at a tiny size every part that the check runs
    in bf16 reads a hundred times the sound reading and more, and every
    piece of the mathematics it gets wrong is refused by the runner's
    limits: the proof that each patch reaches its part. (The limits are
    sized for the bf16 program at the cell's size, so a rounded part of
    this float32 program stays under them; the chip run is what holds
    them.)"""
    from benchmark import limit_check_zaya

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    limit_check_zaya.main(["--seed", "7", "--seq-len", "32", "--batch", "2"])
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    readings = {part: got[0] for part, got in report["readings"].items()}
    assert len(readings) == 10
    sound = readings.pop("as stated")
    assert all(sound[k] <= report["limits"][k] for k in sound)
    for part, reading in readings.items():
        assert reading["nll_rms"] > 100 * sound["nll_rms"], part
        if "bf16" not in part:
            assert any(reading[k] > report["limits"][k] for k in reading), \
                part


PARENT = "766fe15de13d84700c97ba4893118db78fa0f1f3"


def test_no_file_the_benchmark_had_was_changed():
    """Against the parent commit where git has it (a checkout without
    history has nothing to compare and skips): every file under
    ``benchmark/`` that the parent had is there byte for byte, and
    BENCHMARK.json's entries the parent had are a prefix of each list,
    unchanged."""
    def git(*args):
        return subprocess.run(("git", "-C", tiny.ROOT) + args,
                              capture_output=True, text=True)

    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("the parent commit is not in this checkout")
    had = git("ls-tree", "-r", "--name-only", PARENT, "benchmark").stdout
    assert had
    changed = git("diff", "--name-only", PARENT, "--", *had.split()).stdout
    assert changed == ""
    before = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    for key, value in before.items():
        if isinstance(value, list) and value and isinstance(value[0], dict):
            assert now[key][:len(value)] == value, key
        else:
            assert now[key] == value, key
