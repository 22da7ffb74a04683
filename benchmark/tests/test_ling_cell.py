"""The cell ``ling-3.0-flash-t16384`` on the CPU: rehearsed at a tiny size
traced and untraced through ``harness.load_cell`` and the runner,
``flops_ling`` against counts by hand, the sixteen new readers on a
hand-made trace and ``None`` where there is nothing to read, the new entries
held by name, the catalog's row key for key, the gradient and the limit
check at a tiny size, and the proof that no file under ``benchmark/`` that
the parent had was changed.

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

from benchmark import flops_ling, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

tiny.TINY_CONFIGS.setdefault("ling-3.0-flash", dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, v_head_dim=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    qk_head_dim=24, rotary_dim=8, kv_lora_rank=16, intermediate_size=96,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_experts_published=32, num_experts=4, experts_held=[0, 4],
    num_experts_per_tok=4, vocab_size=256, max_position_embeddings=64,
    kda_chunk=16, dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t16384-b1", dict(batch_per_chip=2, seq_len=32))

CELL = "ling-3.0-flash-t16384"
NEW = ("kda_attn_ms", "kda_proj_ms", "kda_conv_gate_ms", "kda_scan_ms",
       "kda_scan_roofline", "ling_mla_attn_ms", "ling_mla_flash_ms",
       "ling_mla_flash_roofline", "ling_moe_ms", "ling_moe_route_ms",
       "ling_moe_experts_ms", "ling_moe_experts_roofline",
       "ling_moe_held_rows_share", "ling_mtp_ms", "ling_head_loss_ms",
       "ling_scan_ms")
HOST = ("ling_moe_held_rows_share",)
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 8, "vocab_size": 19648}
LING = dict(d=2560, n_heads=32, head_dim=128, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            d_ff=6144, d_expert=768, n_experts=512, n_shared_experts=1,
            layer_types=("kda",) * 4 + ("latent_attention", "kda", "kda"),
            mtp_layer_type="latent_attention", num_dense_layers=1,
            n_mtp_modules=1, vocab_rows=19648, seq_len=16384)


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _config():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


def _published():
    """The catalog's row beside the ``model-configs`` guide, where this
    machine has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_ling")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    """Build, lower, reference check, warm-up and a window through the
    harness: every comparison of the runner is printed with its tolerance
    and passes, and the step's own counts are the run's counters."""
    spec = harness.load_cell(CELL, tiny_root)
    assert spec["config"]["hidden_size"] == 64  # the tiny copy
    assert spec["config"]["runner"] == "decoder_ling"
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
        assert 0 < result["metrics"]["ling_moe_held_rows_share"]["value"] < 1
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_runner_builds_the_published_block_at_a_tiny_size(tiny_root):
    """Two steps of the compiled executable outside the harness: the kinds
    are the published layers 1 to 7's, the module's mixer the latent one,
    the step's counts come back with every step, both biases move, and
    the optimizer holds no moments for either."""
    import jax
    import numpy as np

    from benchmark.runners import decoder_ling

    spec = harness.load_cell(CELL, tiny_root)
    job = decoder_ling.build(spec["config"], spec["traffic"],
                             jax.devices()[:1], seed=5)
    cfg = job.cfg
    assert cfg.kinds == ("kda",) * 4 + ("latent_attention", "kda", "kda")
    assert cfg.mtp_kind == "latent_attention"
    assert (cfg.num_dense_layers, cfg.n_mtp_modules) == (1, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert_held,
            cfg.moe_n_group, cfg.moe_topk_group) == (32, 4, 0, 8, 4)
    assert (job.kda["layers"], job.ling_mla["layers"],
            job.moe_share["layers"]) == (6, 2, 7)
    moments = job.opt_state[0].mu
    assert "expert_bias" not in moments and "mtp_expert_bias" not in moments
    assert "k_wqkv" in moments and "mtp_l_wq" in moments
    job.compiled = job.lower().compile()
    job.prepare_reference()
    first = float(job.step())
    assert all(check["ok"] for check in job.compare_reference(first))
    biases = job.biases()
    assert biases.shape == (7, 32) and np.abs(biases).max(axis=1).all()
    second = float(job.step())
    assert second < first
    load = np.asarray(job.readings["load"])
    module = np.asarray(job.readings["mtp_token_nll"])
    assert module.shape == (2, 32) and not module[:, -1].any()
    assert load.shape == (8, 32) and not load[0].any()
    assert (load[1:].sum(axis=1) == 4 * 2 * 32).all()
    assert job.model_flops_per_step > 0
    assert 0 < job.moe_held_rows_share < 1


def test_the_entries_are_the_issues():
    """Held by name, not by place or count: a later PR appends after
    them."""
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["ling-3.0-flash"]
    assert (config["file"], config["source"], config["reduced"]) == (
        "benchmark/configs/ling-3.0-flash.json",
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
        "config.json", list(REDUCED))
    cell = by_name["workloads"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash", "t16384-b1", 1)
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
    assert {m["name"] for m in spec["per_layer"]} == set(NEW + SHARED)
    assert all(CELL not in m.get("workloads", []) or m["name"] in NEW
               for m in bench["per_layer"])
    # The traffic file is the one ``zaya1-8b-t16384`` uses.
    assert by_name["workloads"]["zaya1-8b-t16384"]["traffic"] == \
        cell["traffic"]


def test_the_re_exports_are_the_accepted_readers():
    for new, old in (("ling_moe_ms", "moe_share_ms"),
                     ("ling_moe_route_ms", "moe_share_route_ms"),
                     ("ling_moe_experts_ms", "moe_share_experts_ms"),
                     ("ling_moe_experts_roofline",
                      "moe_share_experts_roofline"),
                     ("ling_moe_held_rows_share", "moe_held_rows_share"),
                     ("ling_mla_attn_ms", "mla_attn_ms"),
                     ("ling_mla_flash_ms", "mla_flash_ms"),
                     ("ling_mtp_ms", "mtp_ms"),
                     ("ling_head_loss_ms", "lat_head_loss_ms")):
        mine, theirs = (importlib.import_module(
            f"benchmark.layer_metrics.{name}") for name in (new, old))
        assert mine.read is theirs.read
        assert (mine.LAYER, mine.UNIT) == (theirs.LAYER, theirs.UNIT)


def test_the_configuration_holds_the_published_keys():
    """The catalog's row key for key; the depth, the dense layers, the
    experts held and the vocabulary are the chip's share, each with its
    published value beside it; no width is among them."""
    from benchmark.runners import decoder_ling

    config, published = _config(), _published()["config"]
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["reduced"]) == set(REDUCED)
    for key, value in REDUCED.items():
        assert config[key] == value
        assert config[key + "_published"] == published[key]
    assert config["source"] == _published()["source_url"]
    assert config["experts_held"] == [0, 8]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["layers_run_published"] == [1, 7]
    assert "64 chips share each layer's experts" in config["deployment"]
    for key in ("full_layer_rule", "kda_qk_norm", "kda_gate", "kda_output",
                "mla_qk_norm", "rotation_layout", "mtp_loss_weight",
                "mtp_module", "bias_rate", "bias_rule", "router",
                "expert_clamp", "loss", "dtype", "optimizer",
                "initialisation", "recompute", "bytes_per_parameter"):
        assert key in config["assumed"], key
    cfg = decoder_ling.transformer_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv,
            cfg.kda_gate_floor, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.moe_n_group, cfg.moe_topk_group,
            cfg.n_shared_experts, cfg.n_mtp_modules, cfg.vocab) == (
        2560, 32, 128, 4, -5.0, 0, 512, 128, 64, 128, 6144, 768, 512, 8, 8,
        8, 4, 1, 1, 19648)
    assert (cfg.route_scale, cfg.rope_theta, cfg.mtp_loss_weight,
            cfg.expert_bias_rate, cfg.norm_eps) == (2.5, 6e6, 0.3, 0.001,
                                                    1e-6)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.pos_table,
            cfg.tie_embeddings) == ("head", "head", False, False)
    # The compile's own count of the parameters is the arithmetic's.
    import jax

    from horovod_tpu.models.transformer import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k, 1),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == 983_941_312
    assert "983,941,312" in config["reduced_why"]


@pytest.mark.parametrize("change, match", [
    (dict(mtp_use_kda=True), "what the program builds"),
    (dict(q_lora_rank=768), "what the program builds"),
    (dict(layers_run_published=[30, 36]), "an expert's clamp"),
    (dict(layer_types=["kda"] * 7), "are not the published layers")])
def test_the_runner_refuses_what_it_does_not_build(change, match):
    from benchmark.runners import decoder_ling

    with pytest.raises(ValueError, match=match):
        decoder_ling.transformer_config(dict(_config(), **change))


# ---- counts by hand ---------------------------------------------------------

def test_model_flops_by_hand():
    # W_q, W_k, W_v, W_f, W_g 2560 x 4096 each, W_o 4096 x 2560, W_beta
    # 2560 x 32.
    kda = flops_ling.kda_matmul_params(2560, 32, 128)
    assert kda == 6 * 10_485_760 + 81_920 == 62_996_480
    # W_q 2560 x 6144, W_kva 2560 x 576, W_kvb 512 x 8192, the gate 2560 x
    # 32, W_o 4096 x 2560.
    mla = flops_ling.latent_attention_matmul_params(2560, 32, 512, 128, 64,
                                                    128)
    assert mla == (15_728_640 + 1_474_560 + 4_194_304 + 81_920
                   + 10_485_760) == 31_965_184
    expert = 3 * 2560 * 768  # 5,898,240
    per_token = flops_ling.ling_train_flops_per_token(
        held_rows_per_token=0.125, **LING)
    sparse = 2560 * 512 + expert + 0.125 * expert
    head = 2560 * 19648
    scan = 18 * 32 * 128 * 128
    scores = 2 * 32 * (4 * 192 + 3 * 128) * 16384 / 2
    assert per_token == pytest.approx(
        6 * (6 * kda + 2 * mla + 3 * 2560 * 6144 + 7 * sparse + 2 * head
             + 2 * 2560 * 2560) + 6 * scan + 2 * scores)
    assert round(per_token / 1e6) == 5215
    # The six KDA mixers are 45 % of it, their scans 1 %; the two latent
    # mixers 31 %, their kernels 23 %.
    assert round(100 * 6 * (6 * kda + scan) / per_token) == 45
    assert round(100 * 6 * scan / per_token) == 1
    assert round(100 * 2 * (6 * mla + scores) / per_token) == 31
    assert round(100 * 2 * scores / per_token) == 23
    # One more held row a token: one more expert in each of seven layers.
    more = flops_ling.ling_train_flops_per_token(
        held_rows_per_token=1.125, **LING)
    assert more - per_token == pytest.approx(7 * 6 * expert)


def test_kernel_operations_and_bytes_by_hand():
    # Tiny scan: B 1, H 2, T 4, K 8, V 16: 18 x 8 x 8 x 16 FLOPs; forward
    # 8 rows x (2 x 8 x 2 + 2 x 16 x 2 + 4 x 8 + 4) B, backward 8 rows x
    # (4 x 8 x 2 + 3 x 16 x 2 + 8 x 8 + 8) B.
    assert flops_ling.kda_scan_train_flops(1, 2, 4, 8, 16) == 18432
    assert flops_ling.kda_scan_train_bytes(1, 2, 4, 8, 16, 2) == \
        8 * 132 + 8 * 232
    # Tiny attention: B 1, H 2, T 4, 12 and 8: 2 x 8 pairs x 2 x (48 + 24).
    assert flops_ling.latent_flash_train_flops(1, 2, 4, 12, 8) == 2304
    assert flops_ling.latent_flash_train_bytes(1, 2, 4, 12, 8, 2) == \
        8 * 2 * (72 + 48)
    # The cell's scan on a v5e: the bytes bound it.
    ops_ms = 1e3 * flops_ling.kda_scan_train_flops(
        1, 32, 16384, 128, 128) / 197e12
    bytes_ms = 1e3 * flops_ling.kda_scan_train_bytes(
        1, 32, 16384, 128, 128, 2) / 819e9
    assert round(ops_ms, 2) == 0.78 and round(bytes_ms, 2) == 2.79
    # The cell's latent mixer: compute bounds it.
    ops_ms = 1e3 * flops_ling.latent_flash_train_flops(
        1, 32, 16384, 192, 128) / 197e12
    bytes_ms = 1e3 * flops_ling.latent_flash_train_bytes(
        1, 32, 16384, 192, 128, 2) / 819e9
    assert round(ops_ms, 1) == 50.2 and round(bytes_ms, 1) == 2.5


# ---- the readers by hand ----------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step
#   %closed_call.1 (a Mosaic call) 200 ns a step
#   %all-reduce.1  250 ns, then 300 ns
#   %fusion.2   100 ns a step, beside %all-reduce.1
#   %fusion.3   100 ns a step
LAYER = "while/body/closed_call/while/body/closed_call"
# A KDA layer: a projection, the scan's kernel, a gate; the expert layer.
KDA_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/kda/kda_proj/"
                 "btd,dchk->btchk/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/kda/"
                      "kda_scan/kda_bwd/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/moe/"
                     "moe_dispatch/gather",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/moe/moe_experts/mul",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/kda/kda_gate/logistic",
}
# The latent layer and the module, and what lies outside every layer.
LATENT_PATHS = {
    "%fusion.1": "jit(s)/jvp(forward)/mtp/head/btd,dv->btv/dot_general",
    "%closed_call.1": "jit(s)/transpose(jvp(forward))/mtp/"
                      "latent_attention/flash_bwd/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/"
                     "latent_attention/mla_kv/btr,rhk->bthk/dot_general",
    "%fusion.2": "jit(s)/jvp(forward)/while/body/dynamic_slice",
    "%fusion.3": "jit(s)/jvp(forward)/loss/reduce_sum",
}


class _Job:
    model_flops_per_step = 0.0
    moe_held_rows_share = 0.015625
    kda = dict(batch=1, heads=2, seq_len=4, k_dim=8, v_dim=16, layers=1,
               itemsize=2)
    ling_mla = dict(batch=1, heads=2, seq_len=4, qk_dim=12, v_dim=8,
                    layers=1, itemsize=2)
    moe_share = dict(d=4, d_expert=8, experts_held=2, layers=1, itemsize=2,
                     rows_held=3.0)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, paths, want", [
    ("kda_attn_ms", KDA_PATHS, (290 + 200 + 100) * NS),
    ("kda_proj_ms", KDA_PATHS, 290 * NS),
    ("kda_conv_gate_ms", KDA_PATHS, 100 * NS),
    ("kda_scan_ms", KDA_PATHS, 200 * NS),
    # 18,432 FLOPs over 1e12 FLOP/s bounds it (2,912 B over 1e12 B/s is
    # less): 18.432 ns a step of 200
    ("kda_scan_roofline", KDA_PATHS, 100 * 18.432 / 200),
    ("ling_mla_attn_ms", KDA_PATHS, None),  # no latent layer in this trace
    ("ling_mla_flash_roofline", KDA_PATHS, None),
    ("ling_mtp_ms", KDA_PATHS, None),
    ("ling_moe_ms", KDA_PATHS, 275 * NS),
    ("ling_moe_route_ms", KDA_PATHS, 275 * NS),
    ("ling_moe_experts_ms", KDA_PATHS, 100 * NS),
    # 1,728 FLOPs over 1e12 bounds it (1,656 B is less): 1.728 ns of 100
    ("ling_moe_experts_roofline", KDA_PATHS, 100 * 1.728 / 100),
    ("ling_moe_held_rows_share", KDA_PATHS, 0.015625),
    ("kda_attn_ms", LATENT_PATHS, None),
    ("ling_mla_attn_ms", LATENT_PATHS, (200 + 275) * NS),
    ("ling_mla_flash_ms", LATENT_PATHS, 200 * NS),
    # 2,304 FLOPs over 1e12 bounds it (1,920 B is less): 2.304 ns of 200
    ("ling_mla_flash_roofline", LATENT_PATHS, 100 * 2.304 / 200),
    ("ling_mtp_ms", LATENT_PATHS, (290 + 200) * NS),
    ("ling_head_loss_ms", LATENT_PATHS, 100 * NS),
    ("ling_scan_ms", LATENT_PATHS, 100 * NS)])
def test_each_new_reader_by_hand(small, metric, paths, want):
    ctx = _scoped(small, paths)
    ctx.job = _Job()
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
    from benchmark import grad_check_ling

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_ling.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    for leaf in ("k_wqkv", "k_A", "k_fb", "l_wq", "l_wgate", "mtp_eh",
                 "mtp_l_wkvb", "wg", "embed"):
        assert f"float32 {leaf} " in out, leaf
    assert "bf16    shared_wgu" in out
    assert json.loads(out.splitlines()[-1])["ok"] is True


def test_limit_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    """In a float32 program every part that the check runs in bf16 and
    every piece of the mathematics it gets wrong moves a reading: that is
    the proof that each patch reaches its part."""
    from benchmark import limit_check_ling

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    limit_check_ling.main(["--seed", "7", "--seq-len", "32", "--batch",
                           "2"])
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert len(report["readings"]) == 10
    sound = report["readings"]["as stated"][0]
    assert all(sound[k] <= report["limits"][k] for k in sound)
    for part, (reading,) in report["readings"].items():
        if part == "as stated":
            continue
        moved = max(reading[k] / max(sound[k], 1e-9) for k in sound)
        assert moved > 3, (part, reading, sound)


# ---- nothing that was there was edited --------------------------------------

PARENT = "3adf648a3fa4f0f2d41bfcb7ab9b39ec2264ae61"


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
