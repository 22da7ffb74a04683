"""The cell ``trinity-mini-t8192`` on the CPU: rehearsed at a tiny size
traced and untraced through ``harness.load_cell`` and the runner,
``flops_afmoe`` against counts by hand, the sixteen new readers on a hand-made
trace and ``None`` where there is nothing to read, the new entries held by
name, the gradient check at a tiny size, and the proof that no file under
``benchmark/`` that the parent had was changed.

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

from benchmark import flops_afmoe, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

SLIDING, FULL = "sliding_attention", "full_attention"
tiny.TINY_CONFIGS.setdefault("trinity-mini", dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=32,
    sliding_window=8, num_experts_published=16, num_experts=4,
    experts_held=[0, 4], num_experts_per_tok=3, vocab_size=256,
    max_position_embeddings=64, dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t8192-b2", dict(batch_per_chip=2, seq_len=32))

CELL = "trinity-mini-t8192"
NEW = ("swa_attn_ms", "full_attn_ms", "swa_flash_ms", "swa_flash_roofline",
       "moe_share_ms", "moe_share_route_ms", "moe_share_experts_ms",
       "moe_share_experts_roofline", "moe_shared_expert_ms",
       "moe_held_rows_share", "share_head_loss_ms", "share_dense_mlp_ms",
       "full_flash_ms", "share_scan_ms", "router_bias_ms",
       "moe_held_max_over_mean")
HOST = ("moe_held_rows_share", "moe_held_max_over_mean")
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
# The catalog's row (model-configs, architectures.jsonl, Trinity-Mini).
PUBLISHED = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
    hidden_size=2048, intermediate_size=6144, load_balance_coeff=0.001,
    max_position_embeddings=131072, model_type="afmoe",
    moe_intermediate_size=1024, mup_enabled=True, n_group=1,
    num_attention_heads=32, num_dense_layers=2, num_expert_groups=1,
    num_experts=128, num_experts_per_tok=8, num_hidden_layers=32,
    num_key_value_heads=4, num_limited_groups=1, num_shared_experts=1,
    rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    route_norm=True, route_scale=2.826, score_func="sigmoid",
    sliding_window=2048, tie_word_embeddings=False, topk_group=1,
    use_grouped_mm=True, vocab_size=200192)
TRINITY = dict(d=2048, n_heads=32, n_kv_heads=4, head_dim=128, d_ff=6144,
               d_expert=1024, n_experts=128, n_shared_experts=1,
               sliding_window=2048, vocab_rows=25024)
PERIOD = [SLIDING, SLIDING, FULL, SLIDING, SLIDING]


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _config():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_trinity")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    """Build, lower, reference check, warm-up and a window through the
    harness: every comparison of the runner is printed with its tolerance
    and passes, and the step's own counts are the run's counters."""
    spec = harness.load_cell(CELL, tiny_root)
    assert spec["config"]["hidden_size"] == 64  # the tiny copy
    assert spec["config"]["runner"] == "decoder_afmoe"
    result = harness.run_cell(CELL, seed=3000000019, seconds=0.2,
                              trace=trace, t_start=time.perf_counter(),
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    said = capsys.readouterr().out
    for what in ("first-step loss vs float32 reference",
                 "every token's cross-entropy of the first step vs float32 "
                 "reference",
                 "the same, the median of the absolute difference",
                 "sum to top_k x tokens (nothing dropped)",
                 "assignments the float32 reference routes elsewhere",
                 "the bias after the first step vs the rule on the step's "
                 "own counts",
                 "tokens per expert, the first step's own counts"):
        assert what in said, what
    if trace:
        # No device plane on the CPU: the device metrics are left out;
        # the two the host has are there.
        assert set(result["metrics"]) == {"host_dispatch_ms", *HOST}
        assert 0 < result["metrics"]["moe_held_rows_share"]["value"] < 1
        assert result["metrics"]["moe_held_max_over_mean"]["value"] >= 1
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_runner_builds_the_published_pattern_at_a_tiny_size(tiny_root):
    """Two steps of the compiled executable outside the harness: the
    step's counts come back with every step, the bias moves, and the
    optimizer holds no moments for it."""
    import jax
    import numpy as np

    from benchmark.runners import decoder_afmoe

    spec = harness.load_cell(CELL, tiny_root)
    job = decoder_afmoe.build(spec["config"], spec["traffic"],
                              jax.devices()[:1], seed=5)
    cfg = job.cfg
    assert cfg.kinds == tuple(PERIOD) and cfg.num_dense_layers == 1
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert_held) == (
        16, 4, 0)
    assert "expert_bias" not in job.opt_state[0].mu
    job.compiled = job.lower().compile()
    job.prepare_reference()
    first = float(job.step())
    assert all(check["ok"] for check in job.compare_reference(first))
    bias = np.asarray(job.params["expert_bias"])
    assert np.abs(bias).max() > 0
    second = float(job.step())
    assert second < first
    load = np.asarray(job.readings["load"])
    assert job.readings["token_nll"].shape == (2, 32)
    assert load.shape == (5, 16) and not load[0].any()
    assert (load[1:].sum(axis=1) == 3 * 2 * 32).all()
    assert np.abs(np.asarray(job.params["expert_bias"]) - bias).max() > 0
    assert job.model_flops_per_step > 0
    assert 0 < job.moe_held_rows_share < 1
    assert job.moe_held_max_over_mean >= 1


def test_the_entries_are_the_issues():
    """Held by name, not by place or count: a later PR appends after
    them."""
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["trinity-mini"]
    assert (config["file"], config["source"], config["reduced"]) == (
        "benchmark/configs/trinity-mini.json",
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json",
        ["num_hidden_layers", "num_dense_layers", "num_experts",
         "vocab_size"])
    cell = by_name["workloads"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "t8192-b2", 1)
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
    # The cell reports the sixteen and what every cell shares; no entry
    # the parent had lists it.
    spec = harness.load_cell(CELL, tiny.ROOT)
    assert {m["name"] for m in spec["per_layer"]} >= set(NEW + SHARED)
    assert all(CELL not in m.get("workloads", []) or m["name"] in NEW
               for m in bench["per_layer"])
    traffic = spec["traffic"]
    assert (traffic["kind"], traffic["batch_per_chip"], traffic["seq_len"],
            traffic["steps_per_chunk"], traffic["chunks_queued"],
            traffic["warmup_steps"], traffic["trace_steps"]) == (
        "token_batches", 2, 8192, 1, 4, 3, 10)


def test_the_configuration_holds_the_published_keys():
    """The catalog's row key for key; the depth, the leading dense
    layers, the experts held and the vocabulary are the chip's share, each
    with its published value beside it. The published ``layer_types``
    stays whole: the runner builds entries 1 to 5."""
    from benchmark.runners import decoder_afmoe

    config = _config()
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size"}
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 16, 25024)
    for key in config["reduced"]:
        assert config[key + "_published"] == PUBLISHED[key]
    assert config["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    kinds = config["layer_types"]
    assert len(kinds) == 32 and kinds == ([SLIDING] * 3 + [FULL]) * 8
    assert list(decoder_afmoe.layer_types(config)) == PERIOD
    assert "eight chips share each layer" in config["deployment"]
    for key in ("bias_rule", "loss", "dtype", "optimizer", "initialisation",
                "recompute", "bytes_per_parameter"):
        assert key in config["assumed"], key
    cfg = decoder_afmoe.transformer_config(config)
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head, cfg.d_ff, cfg.d_expert,
            cfg.n_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.sliding_window, cfg.vocab) == (
        32, 4, 128, 6144, 1024, 128, 16, 8, 2048, 25024)
    assert cfg.embedding_multiplier == 2048 ** 0.5
    assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norms, cfg.rope,
            cfg.pos_table, cfg.tie_embeddings) == (
        "head", True, True, False, False, False)


# ---- counts by hand ---------------------------------------------------------

def test_attended_pairs_by_hand():
    # T 4, W 2: rows see 1, 2, 2, 2 keys.
    assert flops_afmoe.attended_pairs(4, 2) == 7
    assert flops_afmoe.attended_pairs(4) == 8  # T^2 / 2, flops.py's half
    assert flops_afmoe.attended_pairs(4, 4) == 8  # a window of all is none
    # The cell: 14.68 M of the 33.55 M pairs a full layer visits, 44 %.
    swa = flops_afmoe.attended_pairs(8192, 2048)
    assert swa == 2048 * 2049 / 2 + 6144 * 2048 == 14_681_088
    assert flops_afmoe.attended_pairs(8192) == 33_554_432
    assert round(100 * swa / 33_554_432) == 44
    # Three sliding layers do 1.3 times the score work of one full layer.
    assert round(3 * swa / 33_554_432, 1) == 1.3


def test_kernel_operations_and_bytes_by_hand():
    # Tiny: B 1, H 2, T 4, D 8, W 2: 14 x 2 x 8 x 7 pairs.
    assert flops_afmoe.attention_train_flops(1, 2, 4, 8, 2) == 1568
    assert flops_afmoe.attention_train_bytes(1, 2, 4, 8, 2) == 12 * 128
    # With no window it is flops.py's 7 B H T^2 D.
    from benchmark import flops
    assert flops_afmoe.attention_train_flops(2, 32, 8192, 128) == \
        flops.causal_attention_train_flops(2, 32, 8192, 128)
    # The cell's sliding layer on a v5e: compute bounds it.
    ops_ms = 1e3 * flops_afmoe.attention_train_flops(
        2, 32, 8192, 128, 2048) / 197e12
    bytes_ms = 1e3 * flops_afmoe.attention_train_bytes(
        2, 32, 8192, 128, 2) / 819e9
    assert round(ops_ms, 2) == 8.55 and round(bytes_ms, 2) == 1.97
    # The held matmuls. Tiny: 3 rows, d 4, f 8, 2 experts held, bf16:
    # 18 x 3 x 4 x 8; weights 3 x 2 x 4 x 8 x 2 B = 384 B four times,
    # rows 3 x 4 x 2 B = 24 B five times.
    assert flops_afmoe.held_matmul_train_flops(3, 4, 8) == 1728
    assert flops_afmoe.held_matmul_train_bytes(3, 4, 8, 2, 2) == \
        4 * 384 + 5 * 24
    # The cell at balance (16,384 rows on 16 experts): compute bounds it.
    ops_ms = 1e3 * flops_afmoe.held_matmul_train_flops(
        16384, 2048, 1024) / 197e12
    bytes_ms = 1e3 * flops_afmoe.held_matmul_train_bytes(
        16384, 2048, 1024, 16, 2) / 819e9
    assert round(ops_ms, 2) == 3.14 and round(bytes_ms, 2) == 1.39


def test_model_flops_by_hand():
    # Attention: Wq, Wgate, Wo 2048 x 4096 = 8,388,608 each; Wk, Wv
    # 2048 x 512 = 1,048,576 each: 27,262,976.
    attention = flops_afmoe.attention_matmul_params(2048, 32, 4, 128)
    assert attention == 3 * 8_388_608 + 2 * 1_048_576 == 27_262_976
    expert = 3 * 2048 * 1024  # 6,291,456
    per_token = flops_afmoe.afmoe_train_flops_per_token(
        layer_types=PERIOD, num_dense_layers=1, seq_len=8192,
        held_rows_per_token=1.0, **TRINITY)
    sparse = attention + 2048 * 128 + expert + expert  # router, shared, held
    dense = attention + 3 * 2048 * 6144
    swa = 12 * 4096 * 14_681_088 / 8192
    full = 12 * 4096 * 33_554_432 / 8192  # = 6 T (H Dh), twice 6 T d
    assert full == 6 * 8192 * 4096
    assert per_token == pytest.approx(
        6 * (dense + 4 * sparse + 2048 * 25024) + 4 * swa + full)
    assert round(per_token / 1e6) == 2214
    # Twice the rows on held experts: one more expert a token and layer.
    more = flops_afmoe.afmoe_train_flops_per_token(
        layer_types=PERIOD, num_dense_layers=1, seq_len=8192,
        held_rows_per_token=2.0, **TRINITY)
    assert more - per_token == pytest.approx(4 * 6 * expert)
    # The head's slice is 14 % of it.
    assert round(100 * 6 * 2048 * 25024 / per_token) == 14


# ---- the readers by hand ----------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step                   forward, a sliding
#                                                     layer's projection
#   %closed_call.1 (a Mosaic call) 200 ns a step      backward, a sliding
#                                                     layer's flash kernel
#   %all-reduce.1  250 ns, then 300 ns                backward, moe_dispatch
#   %fusion.2   100 ns a step, beside %all-reduce.1   forward, moe_experts
#   %fusion.3   100 ns a step                         forward, the shared
#                                                     expert
LAYER = "while/body/closed_call/while/body/closed_call"
HAND_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/sliding_attention/"
                 "btd,dhk->bthk/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/"
                      "sliding_attention/flash_dq/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                     "moe_dispatch/gather",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_experts/mul",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_shared/"
                 "btd,dcf->btcf/dot_general",
}


# The same events as the blocks no other reader of the cell sees: the
# head, a full layer's kernel, the leading dense layer, a scan's slice of
# a stacked leaf, the bias's rule.
REST_PATHS = {
    "%fusion.1": "jit(s)/jvp(forward)/head/btd,dv->btv/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/"
                      "full_attention/flash_dq/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/mlp/"
                     "btf,fd->btd/dot_general",
    "%fusion.2": "jit(s)/jvp(forward)/while/body/dynamic_slice",
    "%fusion.3": "jit(s)/router_bias/sign",
}


class _ShareJob:
    model_flops_per_step = 0.0
    moe_held_rows_share = 0.125
    moe_held_max_over_mean = 1.25
    swa = dict(batch=1, heads=2, seq_len=4, head_dim=8, window=2, layers=1,
               itemsize=2)
    moe_share = dict(d=4, d_expert=8, experts_held=2, layers=1, itemsize=2,
                     rows_held=3.0)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, want", [
    ("swa_attn_ms", (290 + 200) * NS),
    ("full_attn_ms", None),  # no full layer in the hand-made trace
    ("swa_flash_ms", 200 * NS),
    # 1,568 FLOPs over 1e12 FLOP/s bounds it (1,536 B over 1e12 B/s is
    # less): 1.568 ns a step of 200
    ("swa_flash_roofline", 100 * 1.568 / 200),
    # the gather with the experts' fusion inside it, the shared expert
    ("moe_share_ms", (275 + 100) * NS),
    ("moe_share_route_ms", 275 * NS),
    ("moe_share_experts_ms", 100 * NS),
    # 1,728 FLOPs over 1e12 bounds it (1,656 B is less): 1.728 ns of 100
    ("moe_share_experts_roofline", 100 * 1.728 / 100),
    ("moe_shared_expert_ms", 100 * NS),
    ("moe_held_rows_share", 0.125),
    ("share_head_loss_ms", 290 * NS),
    ("full_flash_ms", 200 * NS),
    ("share_dense_mlp_ms", 275 * NS),
    ("share_scan_ms", 100 * NS),
    ("router_bias_ms", 100 * NS),
    ("moe_held_max_over_mean", 1.25)])
def test_each_new_reader_by_hand(small, metric, want):
    rest = metric in NEW[10:] and metric not in HOST
    ctx = _scoped(small, REST_PATHS if rest else HAND_PATHS)
    ctx.job = _ShareJob()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    got = _read(metric, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("paths", [
    {},  # the parent's trace, or the CPU's: no path at all
    {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    """Where the program has none of the scopes, as the parent has not,
    every reader returns None and does not raise: with this cell's job,
    and with a job that knows nothing of the cell."""
    for job in (_ShareJob(), None):
        ctx = _scoped(small, paths)
        if job is not None:
            ctx.job = job
            # no first step was run
            ctx.job.moe_held_rows_share = None
            ctx.job.moe_held_max_over_mean = None
        ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
        for metric in NEW:
            assert _read(metric, ctx) is None, metric


def test_another_cells_expert_layer_is_not_this_cells(small):
    """``moe`` is ``moe_ms``'s scope to read where every expert is held:
    a job that states no share gets none of the share's metrics."""
    ctx = _scoped(small, HAND_PATHS)  # ctx.job is the plain decoder's
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    for metric in ("moe_share_ms", "moe_share_route_ms",
                   "moe_share_experts_ms", "moe_share_experts_roofline",
                   "swa_flash_roofline", "moe_held_rows_share",
                   "moe_held_max_over_mean"):
        assert _read(metric, ctx) is None, metric
    assert _read("moe_ms", ctx) == pytest.approx(375 * NS)
    # Nor the head, the dense layer and the scans, whose scopes the other
    # cells' own metrics read.
    ctx = _scoped(small, REST_PATHS)
    for metric in ("share_head_loss_ms", "share_dense_mlp_ms",
                   "share_scan_ms"):
        assert _read(metric, ctx) is None, metric
    assert _read("head_loss_ms", ctx) == pytest.approx(290 * NS)


# ---- the gradient check, at a tiny size -------------------------------------

def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_afmoe

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_afmoe.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    assert "float32 wgate" in out and "bf16    shared_wgu" in out
    assert json.loads(out.splitlines()[-1])["ok"] is True


def test_limit_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    """In a float32 program every part that the check runs in bf16 and
    every piece of the mathematics it gets wrong must be refused by one
    of the runner's limits: that is the proof that each patch reaches its
    part."""
    from benchmark import limit_check_afmoe

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert limit_check_afmoe.main(["--seed", "7", "--seq-len", "32"]) == 0
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert set(report["readings"]) == set(limit_check_afmoe.PARTS)
    assert report["ok"] is True


# ---- nothing that was there was edited --------------------------------------

PARENT = "7db8c937bbea7ce8ebc0d4904eb60433d92a8669"


def test_no_file_the_benchmark_had_was_changed():
    """Against the parent commit where git has it (a checkout without
    history, as the chip's copy or the driver's, has nothing to compare
    and skips): every file under ``benchmark/`` that the parent had is
    there byte for byte, and BENCHMARK.json's entries the parent had are
    a prefix of each list, unchanged."""
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
