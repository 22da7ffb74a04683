"""The cell ``glm-4.7-flash-t8192`` on the CPU: rehearsed at a tiny size
traced and untraced through ``harness.load_cell`` and the runner,
``flops_glm_lite`` against counts by hand, the fifteen new readers on a
hand-made trace and ``None`` where there is nothing to read, the new
entries held by name, the gradient and the limit check at a tiny size, and
the proof that no file under ``benchmark/`` that the parent had was
changed.

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

from benchmark import flops_glm_lite, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

tiny.TINY_CONFIGS.setdefault("glm-4.7-flash", dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    v_head_dim=16, qk_nope_head_dim=12, qk_rope_head_dim=4, q_lora_rank=24,
    kv_lora_rank=16, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts_published=16, n_routed_experts=4, experts_held=[0, 4],
    num_experts_per_tok=3, vocab_size=256, max_position_embeddings=64,
    dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t8192-b2", dict(batch_per_chip=2, seq_len=32))

CELL = "glm-4.7-flash-t8192"
NEW = ("mla_attn_ms", "mla_lowrank_ms", "mla_flash_ms", "mla_flash_roofline",
       "mtp_ms", "mtp_head_loss_ms", "lat_moe_ms", "lat_moe_route_ms",
       "lat_moe_experts_ms", "lat_moe_experts_roofline", "lat_moe_shared_ms",
       "lat_moe_held_rows_share", "lat_dense_mlp_ms", "lat_head_loss_ms",
       "lat_scan_ms")
HOST = ("lat_moe_held_rows_share",)
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
# The catalog's row (model-configs, architectures.jsonl, GLM-4.7-Flash).
PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4,
    first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
    num_nextn_predict_layers=1, partial_rotary_factor=1, rms_norm_eps=1e-05,
    rope_scaling=None, rope_theta=1000000, tie_word_embeddings=False,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, vocab_size=154880)
GLM = dict(d=2048, n_heads=20, q_lora_rank=768, kv_lora_rank=512,
           qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
           d_ff=10240, d_expert=1536, n_experts=64, n_shared_experts=1,
           n_layers=5, num_dense_layers=1, n_mtp_modules=1,
           vocab_rows=19360, seq_len=8192)


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _config():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_glm")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    """Build, lower, reference check, warm-up and a window through the
    harness: every comparison of the runner is printed with its tolerance
    and passes, and the step's own counts are the run's counters."""
    spec = harness.load_cell(CELL, tiny_root)
    assert spec["config"]["hidden_size"] == 64  # the tiny copy
    assert spec["config"]["runner"] == "decoder_glm_lite"
    result = harness.run_cell(CELL, seed=3000000019, seconds=0.2,
                              trace=trace, t_start=time.perf_counter(),
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    said = capsys.readouterr().out
    for what in ("first-step loss (main + 0.3 x module) vs float32 "
                 "reference",
                 "every token's main cross-entropy of the first step vs "
                 "float32 reference",
                 "the same, the median of the absolute difference",
                 "every token's cross-entropy in the multi-token-prediction "
                 "module vs float32 reference",
                 "the module's, the median of the absolute difference",
                 "sum to top_k x tokens (nothing dropped)",
                 "assignments the float32 reference routes elsewhere",
                 "every bias after the first step vs the rule on the "
                 "step's own counts",
                 "tokens per expert, the first step's own counts"):
        assert what in said, what
    if trace:
        # No device plane on the CPU: the device metrics are left out;
        # the one the host has is there.
        assert set(result["metrics"]) == {"host_dispatch_ms", *HOST}
        assert 0 < result["metrics"]["lat_moe_held_rows_share"]["value"] < 1
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_runner_builds_the_published_block_at_a_tiny_size(tiny_root):
    """Two steps of the compiled executable outside the harness: the
    step's counts come back with every step, the module's layer as the
    last row, both biases move, and the optimizer holds no moments for
    either."""
    import jax
    import numpy as np

    from benchmark.runners import decoder_glm_lite

    spec = harness.load_cell(CELL, tiny_root)
    job = decoder_glm_lite.build(spec["config"], spec["traffic"],
                                 jax.devices()[:1], seed=5)
    cfg = job.cfg
    assert cfg.kinds == ("latent_attention",) * 5
    assert (cfg.num_dense_layers, cfg.n_mtp_modules) == (1, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert_held) == (
        16, 4, 0)
    assert (job.mla["layers"], job.moe_share["layers"]) == (6, 5)
    moments = job.opt_state[0].mu
    assert "expert_bias" not in moments and "mtp_expert_bias" not in moments
    assert "mtp_eh" in moments and "mtp_l_wqa" in moments
    job.compiled = job.lower().compile()
    job.prepare_reference()
    first = float(job.step())
    assert all(check["ok"] for check in job.compare_reference(first))
    biases = job.biases()
    assert biases.shape == (5, 16) and np.abs(biases).max(axis=1).all()
    second = float(job.step())
    assert second < first
    load = np.asarray(job.readings["load"])
    assert job.readings["token_nll"].shape == (2, 32)
    module = np.asarray(job.readings["mtp_token_nll"])
    assert module.shape == (2, 32) and not module[:, -1].any()
    assert module[:, :-1].all()
    assert load.shape == (6, 16) and not load[0].any()
    assert (load[1:].sum(axis=1) == 3 * 2 * 32).all()
    assert np.abs(job.biases() - biases).max(axis=1).all()
    assert job.model_flops_per_step > 0
    assert 0 < job.moe_held_rows_share < 1


def test_the_entries_are_the_issues():
    """Held by name, not by place or count: a later PR appends after
    them."""
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["glm-4.7-flash"]
    assert (config["file"], config["source"], config["reduced"]) == (
        "benchmark/configs/glm-4.7-flash.json",
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json",
        ["num_hidden_layers", "n_routed_experts", "vocab_size"])
    cell = by_name["workloads"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash", "t8192-b2", 1)
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
    # The cell reports the fifteen and what every cell shares; no entry
    # the parent had lists it.
    spec = harness.load_cell(CELL, tiny.ROOT)
    assert {m["name"] for m in spec["per_layer"]} == set(NEW + SHARED)
    assert all(CELL not in m.get("workloads", []) or m["name"] in NEW
               for m in bench["per_layer"])
    # The traffic file is the one ``trinity-mini-t8192`` uses.
    assert by_name["workloads"]["trinity-mini-t8192"]["traffic"] == \
        cell["traffic"]


def test_the_re_exports_are_the_accepted_readers():
    """Nine of the fifteen read scopes that accepted readers read in
    ``trinity-mini-t8192``: six are those readers' own ``read``."""
    for new, old in (("lat_moe_ms", "moe_share_ms"),
                     ("lat_moe_route_ms", "moe_share_route_ms"),
                     ("lat_moe_experts_ms", "moe_share_experts_ms"),
                     ("lat_moe_experts_roofline",
                      "moe_share_experts_roofline"),
                     ("lat_moe_shared_ms", "moe_shared_expert_ms"),
                     ("lat_moe_held_rows_share", "moe_held_rows_share")):
        mine, theirs = (importlib.import_module(
            f"benchmark.layer_metrics.{name}") for name in (new, old))
        assert mine.read is theirs.read
        assert (mine.LAYER, mine.UNIT) == (theirs.LAYER, theirs.UNIT)


def test_the_configuration_holds_the_published_keys():
    """The catalog's row key for key; the depth, the experts held and the
    vocabulary are the chip's share, each with its published value beside
    it; no width is among them."""
    from benchmark.runners import decoder_glm_lite

    config = _config()
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    for key in config["reduced"]:
        assert config[key + "_published"] == PUBLISHED[key]
    assert config["experts_held"] == [0, 8]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert "eight chips share each layer" in config["deployment"]
    for key in ("mtp_loss_weight", "mtp_projection_order", "mtp_input",
                "rotation_layout", "bias_rate", "bias_rule", "loss",
                "dtype", "optimizer", "initialisation", "recompute",
                "bytes_per_parameter"):
        assert key in config["assumed"], key
    cfg = decoder_glm_lite.transformer_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.d_head, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.n_shared_experts, cfg.n_mtp_modules,
            cfg.vocab) == (2048, 20, 256, 768, 512, 192, 64, 10240, 1536,
                           64, 8, 4, 1, 1, 19360)
    assert (cfg.route_scale, cfg.rope_theta, cfg.mtp_loss_weight,
            cfg.expert_bias_rate) == (1.8, 1e6, 0.3, 0.001)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norms, cfg.pos_table,
            cfg.tie_embeddings) == (False, False, False, False, False)


# ---- counts by hand ---------------------------------------------------------

def test_model_flops_by_hand():
    # W_qa 2048 x 768, W_qb 768 x 5120, W_kva 2048 x 576, W_kvb 512 x 8960
    # (20 heads of 192 + 256), W_o 5120 x 2048.
    mixer = flops_glm_lite.latent_attention_matmul_params(
        2048, 20, 768, 512, 192, 64, 256)
    assert mixer == (1_572_864 + 3_932_160 + 1_179_648 + 4_587_520
                     + 10_485_760) == 21_757_952
    expert = 3 * 2048 * 1536  # 9,437,184
    per_token = flops_glm_lite.glm_lite_train_flops_per_token(
        held_rows_per_token=0.5, **GLM)
    sparse = 2048 * 64 + expert + 0.5 * expert  # router, shared, held
    head = 2048 * 19360
    scores = 12 * 5120 * 8192 / 2  # 12 H Dh T / 2 a mixer
    assert per_token == pytest.approx(
        6 * (6 * mixer + 3 * 2048 * 10240 + 5 * sparse + 2 * head
             + 2 * 2048 * 2048) + 6 * scores)
    assert round(per_token / 1e6) == 3625
    # The six mixers are 63 % of it, their kernels 42 %; the rest of the
    # module (projection, head, experts) 10 %.
    assert round(100 * 6 * (6 * mixer + scores) / per_token) == 63
    assert round(100 * 6 * scores / per_token) == 42
    assert round(100 * 6 * (2 * 2048 * 2048 + head + sparse)
                 / per_token) == 10
    # One more held row a token: one more expert in each of five layers.
    more = flops_glm_lite.glm_lite_train_flops_per_token(
        held_rows_per_token=1.5, **GLM)
    assert more - per_token == pytest.approx(5 * 6 * expert)


def test_kernel_operations_and_bytes_by_hand():
    from benchmark import flops

    # Tiny: B 1, H 2, T 4, D 8: 7 x 2 x 16 x 8; twelve arrays of 64 x 2 B.
    assert flops_glm_lite.latent_flash_train_flops(1, 2, 4, 8) == 1792
    assert flops_glm_lite.latent_flash_train_bytes(1, 2, 4, 8, 2) == 1536
    assert flops_glm_lite.latent_flash_train_flops(2, 20, 8192, 256) == \
        flops.causal_attention_train_flops(2, 20, 8192, 256)
    # The cell's mixer on a v5e: compute bounds it.
    ops_ms = 1e3 * flops_glm_lite.latent_flash_train_flops(
        2, 20, 8192, 256) / 197e12
    bytes_ms = 1e3 * flops_glm_lite.latent_flash_train_bytes(
        2, 20, 8192, 256, 2) / 819e9
    assert round(ops_ms, 1) == 24.4 and round(bytes_ms, 1) == 2.5
    # The held matmuls at balance (8,192 rows on 8 experts of 2,048 x
    # 1,536): compute bounds them.
    ops_ms = 1e3 * flops_glm_lite.held_matmul_train_flops(
        8192, 2048, 1536) / 197e12
    bytes_ms = 1e3 * flops_glm_lite.held_matmul_train_bytes(
        8192, 2048, 1536, 8, 2) / 819e9
    assert round(ops_ms, 2) == 2.35 and round(bytes_ms, 2) == 0.94


# ---- the readers by hand ----------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step
#   %closed_call.1 (a Mosaic call) 200 ns a step
#   %all-reduce.1  250 ns, then 300 ns
#   %fusion.2   100 ns a step, beside %all-reduce.1
#   %fusion.3   100 ns a step
LAYER = "while/body/closed_call/while/body/closed_call"
# The stack's layers: a low-rank projection, a kernel, the expert layer.
STACK_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/latent_attention/mla_kv/"
                 "btr,rhk->bthk/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/"
                      "latent_attention/flash_dq/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                     "moe_dispatch/gather",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_experts/mul",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_shared/"
                 "btd,dcf->btcf/dot_general",
}
# The module, and what lies outside every layer.
MODULE_PATHS = {
    "%fusion.1": "jit(s)/jvp(forward)/mtp/head/btd,dv->btv/dot_general",
    "%closed_call.1": "jit(s)/transpose(jvp(forward))/mtp/"
                      "latent_attention/flash_dkv/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/mlp/"
                     "btf,fd->btd/dot_general",
    "%fusion.2": "jit(s)/jvp(forward)/while/body/dynamic_slice",
    "%fusion.3": "jit(s)/jvp(forward)/loss/reduce_sum",
}


class _Job:
    model_flops_per_step = 0.0
    moe_held_rows_share = 0.125
    mla = dict(batch=1, heads=2, seq_len=4, head_dim=8, layers=1, itemsize=2)
    moe_share = dict(d=4, d_expert=8, experts_held=2, layers=1, itemsize=2,
                     rows_held=3.0)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, paths, want", [
    ("mla_attn_ms", STACK_PATHS, (290 + 200) * NS),
    ("mla_lowrank_ms", STACK_PATHS, 290 * NS),
    ("mla_flash_ms", STACK_PATHS, 200 * NS),
    # 1,792 FLOPs over 1e12 FLOP/s bounds it (1,536 B over 1e12 B/s is
    # less): 1.792 ns a step of 200
    ("mla_flash_roofline", STACK_PATHS, 100 * 1.792 / 200),
    ("mtp_ms", STACK_PATHS, None),  # no module in the stack's trace
    ("mtp_head_loss_ms", STACK_PATHS, None),
    # the gather with the experts' fusion inside it, the shared expert
    ("lat_moe_ms", STACK_PATHS, (275 + 100) * NS),
    ("lat_moe_route_ms", STACK_PATHS, 275 * NS),
    ("lat_moe_experts_ms", STACK_PATHS, 100 * NS),
    # 1,728 FLOPs over 1e12 bounds it (1,656 B is less): 1.728 ns of 100
    ("lat_moe_experts_roofline", STACK_PATHS, 100 * 1.728 / 100),
    ("lat_moe_shared_ms", STACK_PATHS, 100 * NS),
    ("lat_moe_held_rows_share", STACK_PATHS, 0.125),
    # The module's kernel is a latent mixer's too; its head is not the
    # main head, whose loss is; the stack's dense layer; a scan's slice.
    ("mla_attn_ms", MODULE_PATHS, 200 * NS),
    ("mla_flash_ms", MODULE_PATHS, 200 * NS),
    ("mtp_ms", MODULE_PATHS, (290 + 200) * NS),
    ("mtp_head_loss_ms", MODULE_PATHS, 290 * NS),
    ("lat_head_loss_ms", MODULE_PATHS, 100 * NS),
    ("lat_dense_mlp_ms", MODULE_PATHS, 275 * NS),
    ("lat_scan_ms", MODULE_PATHS, 100 * NS)])
def test_each_new_reader_by_hand(small, metric, paths, want):
    ctx = _scoped(small, paths)
    ctx.job = _Job()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    got = _read(metric, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_modules_own_blocks_are_not_the_stacks(small):
    """A dense MLP, a head or a scan-like slice under ``mtp`` is
    ``mtp_ms``'s: the three readers that state a selection of their own
    leave it out."""
    under = {k: v.replace("jit(s)/jvp(forward)/", "jit(s)/jvp(forward)/mtp/")
             .replace("transpose(jvp(forward))/",
                      "transpose(jvp(forward))/mtp/")
             for k, v in MODULE_PATHS.items()}
    ctx = _scoped(small, under)
    ctx.job = _Job()
    for metric in ("lat_head_loss_ms", "lat_dense_mlp_ms", "lat_scan_ms"):
        assert _read(metric, ctx) is None, metric
    assert _read("mtp_ms", ctx) is not None


@pytest.mark.parametrize("paths", [
    {},  # the parent's trace, or the CPU's: no path at all
    {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    """Where the program has none of the scopes, as the parent has not,
    every reader returns None and does not raise: with this cell's job,
    and with a job that knows nothing of the cell."""
    for job in (_Job(), None):
        ctx = _scoped(small, paths)
        if job is not None:
            ctx.job = job
            ctx.job.moe_held_rows_share = None  # no first step was run
        ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
        for metric in NEW:
            assert _read(metric, ctx) is None, metric


# ---- the two checks, at a tiny size -----------------------------------------

def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_glm_lite

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_glm_lite.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    for leaf in ("l_wqa", "l_wkvb", "mtp_eh", "mtp_l_wqb", "wg", "embed"):
        assert f"float32 {leaf} " in out, leaf
    assert "bf16    shared_wgu" in out
    assert json.loads(out.splitlines()[-1])["ok"] is True


def test_limit_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    """In a float32 program every part that the check runs in bf16 and
    every piece of the mathematics it gets wrong must be refused by one
    of the runner's limits: that is the proof that each patch reaches its
    part."""
    from benchmark import limit_check_glm_lite

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert limit_check_glm_lite.main(["--seed", "7", "--seq-len", "32"]) == 0
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert len(report["readings"]) == 11 and report["ok"] is True
    sound = report["readings"]["as stated"][0]
    for part, (reading,) in report["readings"].items():
        if part.startswith("the module"):  # the stack is as stated
            assert reading["nll_rms"] == sound["nll_rms"], part


# ---- nothing that was there was edited --------------------------------------

PARENT = "196e2f37b7ffc3fe117e7f88cd24343695154b34"


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
