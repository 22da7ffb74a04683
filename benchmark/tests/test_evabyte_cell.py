"""The cell ``evabyte-t32768`` on the CPU: rehearsed at a tiny size traced
and untraced through ``harness.load_cell`` and the runner, ``flops_eva``
against counts by hand, the eleven new readers on a hand-made trace and
``None`` where there is nothing to read, the new entries held by name, the
configuration held to the catalog's row key for key, the gradient and the
limit check at a tiny size, and the proof that no file under
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

from benchmark import flops_eva, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

tiny.TINY_CONFIGS.setdefault("evabyte", dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=96, vocab_size=40, window_size=32, chunk_size=4,
    max_position_embeddings=128, max_seq_length=128, num_hidden_layers=2,
    mlp_block_tokens=48, dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t32768-b1", dict(batch_per_chip=2,
                                               seq_len=128))

CELL = "evabyte-t32768"
NEW = ("eva_attn_ms", "eva_proj_ms", "eva_chunks_ms", "eva_merge_ms",
       "eva_local_flash_ms", "eva_remote_flash_ms", "eva_flash_ms",
       "eva_flash_roofline", "eva_mlp_ms", "eva_head_loss_ms",
       "eva_scan_ms")
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
EVA = dict(d=4096, n_heads=32, head_dim=128, d_ff=11008, n_layers=4,
           vocab_rows=320, n_pred_heads=8, seq_len=32768, window=2048,
           chunk=16)


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _published():
    """The catalog's row (model-configs, architectures.jsonl, EvaByte),
    where the guide is installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not installed here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "EvaByte")


def _config():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "evabyte.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_eva")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    """Build, lower, reference check, warm-up and a window through the
    harness: every comparison of the runner is printed with its tolerance
    and passes."""
    spec = harness.load_cell(CELL, tiny_root)
    assert spec["config"]["hidden_size"] == 64  # the tiny copy
    assert spec["config"]["runner"] == "decoder_eva"
    result = harness.run_cell(CELL, seed=3000000019, seconds=0.2,
                              trace=trace, t_start=time.perf_counter(),
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    said = capsys.readouterr().out
    for what in ("first-step loss vs float32 reference",
                 "every position's cross-entropy under each of the 8 heads "
                 "of the first step vs float32 reference",
                 "the same, the median of the absolute difference"):
        assert what in said, what
    if trace:
        assert set(result["metrics"]) == {"host_dispatch_ms"}
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_entries_are_the_issues():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["evabyte"]
    assert (config["file"], config["source"], config["reduced"]) == (
        "benchmark/configs/evabyte.json",
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json",
        ["num_hidden_layers"])
    cell = by_name["workloads"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte", "t32768-b1", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for name in NEW:
        metric = by_name["per_layer"][name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "samples_per_s_chip"
        assert metric["source"] == "device_trace"
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert (reader.LAYER, reader.UNIT) == (metric["layer"],
                                               metric["unit"])
    spec = harness.load_cell(CELL, tiny.ROOT)
    assert {m["name"] for m in spec["per_layer"]} == set(NEW + SHARED)
    assert all(CELL not in m.get("workloads", []) or m["name"] in NEW
               for m in bench["per_layer"])
    assert [c for c in bench["workloads"] if c["config"] == "evabyte"] == [
        cell]
    traffic = spec["traffic"]
    assert (traffic["kind"], traffic["seq_len"], traffic["batch_per_chip"],
            traffic["steps_per_chunk"], traffic["chunks_queued"]) == (
        "token_batches", 32768, 1, 1, 2)


def test_the_configuration_holds_the_published_keys():
    """The catalog's row key for key; the depth alone is cut, its
    published value beside it; no width is changed."""
    from benchmark.runners import decoder_eva

    config, published = _config(), _published()
    assert config["source"] == published["source_url"]
    changed = {k for k, v in published["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers_published"] == \
        published["config"]["num_hidden_layers"] == 32
    assert "eight pipeline stages of four layers" in config["deployment"]
    for key in ("float32_stream", "pooling_vectors", "summaries",
                "head_weights", "labels", "norms", "dtype", "optimizer",
                "initialisation", "layout", "recompute", "mlp_block",
                "bytes_per_parameter"):
        assert key in config["assumed"], key
    for key in ("memory", "flops"):
        assert "PLACEHOLDER" not in config[key]
    cfg = decoder_eva.transformer_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head, cfg.d_ff,
            cfg.vocab, cfg.n_layers, cfg.eva_window, cfg.eva_chunk,
            cfg.n_pred_heads, cfg.max_seq) == (
        4096, 32, 32, 128, 11008, 320, 4, 2048, 16, 8, 32768)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e5, 1e-5)
    assert cfg.kinds == ("eva",) * 4
    assert cfg.float32_stream and cfg.norm_unit_offset and cfg.gated_mlp
    assert not cfg.tie_embeddings and not cfg.pos_table and cfg.remat


def test_the_runner_refuses_what_the_program_does_not_build():
    from benchmark.runners import decoder_eva

    for key, value in (("attention_class", "softmax"), ("fp32_ln", True),
                       ("num_key_value_heads", 8), ("fp32_skip_add", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="decoder_eva runner"):
            decoder_eva.transformer_config(dict(_config(), **{key: value}))


def test_model_flops_by_hand():
    # Four d x d projections and three d x 11,008 matrices a layer.
    layer = flops_eva.eva_matmul_params(4096, 32, 128, 11008)
    assert layer == 4 * 16_777_216 + 3 * 45_088_768 == 202_375_168
    exact, summary = flops_eva.eva_pairs(32768, 2048, 16)
    # 16 windows x 2,048 x 2,049 / 2; 2,048 queries x 128 summaries x (0 +
    # 1 + ... + 15) earlier windows.
    assert exact == 16 * 2_098_176 == 33_570_816
    assert summary == 2048 * 128 * 120 == 31_457_280
    per_token = flops_eva.eva_train_flops_per_token(**EVA)
    pairs = (exact + summary) / 32768
    head = 4096 * 8 * 320
    assert per_token == pytest.approx(
        4 * (6 * layer + 12 * 4096 * pairs) + 6 * head)
    assert round(per_token / 1e6) == 5310
    assert round(per_token * 32768 / 1e12) == 174
    # The mixers' attention is 7 % of it, the layers' matmuls 91 %, the
    # eight heads 1 %.
    assert round(100 * 4 * 12 * 4096 * pairs / per_token) == 7
    assert round(100 * 4 * 6 * layer / per_token) == 91
    assert round(100 * 6 * head / per_token) == 1
    # One window: no summary, the causal triangle.
    assert flops_eva.eva_pairs(2048, 2048, 16) == (2_098_176, 0)
    assert flops_eva.eva_pairs(1024, 2048, 16) == (1024 * 1025 // 2, 0)


def test_kernel_operations_and_bytes_by_hand():
    # Tiny: B 1, H 2, D 8, T 8, W 4, C 2: exact 2 x 10 pairs, summaries 4
    # x 2 x 1; 14 x 2 x 8 a pair. Twelve arrays of 8 rows and six of 4, 2
    # heads x 8 channels x 2 B.
    assert flops_eva.eva_pairs(8, 4, 2) == (20, 8)
    assert flops_eva.eva_flash_train_flops(1, 2, 8, 8, 4, 2) == 14 * 16 * 28
    assert flops_eva.eva_flash_train_bytes(1, 2, 8, 8, 2, 2) == \
        (12 * 8 + 6 * 4) * 32
    ops_ms = 1e3 * flops_eva.eva_flash_train_flops(
        1, 32, 128, 32768, 2048, 16) / 197e12
    bytes_ms = 1e3 * flops_eva.eva_flash_train_bytes(
        1, 32, 128, 32768, 16, 2) / 819e9
    assert round(ops_ms, 1) == 18.9 and round(bytes_ms, 1) == 4.1


# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1 140 + 150 ns a step; %closed_call.1 (a Mosaic call) 200 ns;
#   %all-reduce.1 250 ns, then 300 ns; %fusion.2 100 ns, beside it;
#   %fusion.3 100 ns a step
LAYER = "while/body/closed_call/while/body/closed_call"
MIXER_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/eva/eva_qkv/btd,dchk->btchk/"
                 "dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/eva/"
                      "eva_local/flash_bwd/pallas_call",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/eva/"
                     "eva_chunks/reduce_sum",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/eva/eva_merge/mul",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/eva/eva_out/"
                 "bthk,hkd->btd/dot_general",
}
REST_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/mlp/while/body/checkpoint/"
                 "btd,dcf->btcf/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/eva/"
                      "eva_remote/flash_bwd/pallas_call",
    "%all-reduce.1": "jit(s)/transpose(jvp(forward))/head/btd,dv->btv/"
                     "dot_general",
    "%fusion.2": "jit(s)/jvp(forward)/while/body/dynamic_slice",
    "%fusion.3": "jit(s)/jvp(forward)/loss/reduce_max",
}


class _Job:
    model_flops_per_step = 0.0
    eva = dict(batch=1, heads=2, head_dim=8, seq_len=8, window=4, chunk=2,
               layers=1, itemsize=2)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, paths, want", [
    # the pooling with the merge's fusion inside it
    ("eva_attn_ms", MIXER_PATHS, (290 + 200 + 275 + 100) * NS),
    ("eva_proj_ms", MIXER_PATHS, (290 + 100) * NS),
    ("eva_chunks_ms", MIXER_PATHS, 275 * NS),
    ("eva_merge_ms", MIXER_PATHS, 100 * NS),
    ("eva_local_flash_ms", MIXER_PATHS, 200 * NS),
    ("eva_remote_flash_ms", MIXER_PATHS, None),
    ("eva_flash_ms", MIXER_PATHS, 200 * NS),
    # 6,272 FLOPs over 1e12 FLOP/s bounds it (3,840 B over 1e12 B/s is
    # less): 6.272 ns a step of 200
    ("eva_flash_roofline", MIXER_PATHS, 100 * 6.272 / 200),
    ("eva_mlp_ms", MIXER_PATHS, None),
    ("eva_head_loss_ms", MIXER_PATHS, None),
    ("eva_scan_ms", MIXER_PATHS, None),
    ("eva_mlp_ms", REST_PATHS, 290 * NS),
    ("eva_remote_flash_ms", REST_PATHS, 200 * NS),
    ("eva_local_flash_ms", REST_PATHS, None),
    ("eva_flash_ms", REST_PATHS, 200 * NS),
    ("eva_head_loss_ms", REST_PATHS, (275 + 100) * NS),
    ("eva_scan_ms", REST_PATHS, 100 * NS),
    ("eva_chunks_ms", REST_PATHS, None)])
def test_each_new_reader_by_hand(small, metric, paths, want):
    ctx = _scoped(small, paths)
    ctx.job = _Job()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    got = _read(metric, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("paths", [
    {}, {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"},
    # another cell's program: its blocks are not this cell's to read
    {"%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/mlp/dot_general",
     "%fusion.3": "jit(s)/jvp(forward)/head/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    """Where the program has none of the scopes every reader returns None
    and does not raise: with this cell's job where nothing is scoped, and
    with a job that knows nothing of the cell whatever is."""
    for job in (_Job(), None):
        if job is not None and len(paths) == 2:
            continue
        ctx = _scoped(small, paths)
        ctx.job = job
        ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
        for metric in NEW:
            assert _read(metric, ctx) is None, metric


def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_eva

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_eva.main(["--seed", "7", "--seq-len", "128"]) == 0
    out = capsys.readouterr().out
    for leaf in ("e_wqkv", "e_mu", "e_phi", "e_wo", "wgu", "w2", "ln1",
                 "final_ln", "head", "embed"):
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
    from benchmark import limit_check_eva

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    limit_check_eva.main(["--seed", "7", "--seq-len", "128", "--batch",
                          "2"])
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    readings = {part: got[0] for part, got in report["readings"].items()}
    assert len(readings) == len(limit_check_eva.PARTS) >= 9
    sound = readings.pop("as stated")
    assert all(sound[k] <= report["limits"][k] for k in sound)
    for part, reading in readings.items():
        assert reading["nll_rms"] > 100 * sound["nll_rms"], part
        if "bf16" not in part:
            assert any(reading[k] > report["limits"][k] for k in reading), \
                part


PARENT = "87269ff94c1cf2b2751e4aac746d317ce8ec2ca3"


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
    added = {key: [e["name"] for e in now[key][len(before[key]):]]
             for key in ("configs", "workloads", "per_layer")}
    assert added == {"configs": ["evabyte"], "workloads": [CELL],
                     "per_layer": list(NEW)}
