"""The cell ``granite-h-t8192`` on the CPU: rehearsed at a tiny size
traced and untraced through ``harness.run_cell``, ``flops_ssd`` against
counts by hand, the four new readers on a hand-made trace and ``None``
where there is nothing to read, the new entries held by name, and the
gradient check and the limit check at a tiny size.

The cell's tiny sizes are registered here, as this module is imported
(``benchmark/conftest.py``, which registers OLMoE's, is not this PR's to
edit): ``test_harness.py`` rehearses every cell of BENCHMARK.json, so run
it with this file collected (``pytest benchmark/tests``), never alone."""
import importlib
import json
import os
import time

import pytest

from benchmark import flops_ssd, harness
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import _scoped
from benchmark.tests.test_trace_reduce import _load

tiny.TINY_CONFIGS.setdefault("granite-4.0-h-micro", dict(
    hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.0625, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    max_position_embeddings=64, vocab_size=256, dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t8192-b1", dict(batch_per_chip=2, seq_len=32))

NEW = ("mamba_ms", "ssd_ms", "ssd_roofline", "gated_mlp_ms")
SHARED = ("host_dispatch_ms", "step_device_ms", "step_mfu_pct",
          "device_idle_pct", "fwd_ms", "bwd_ms", "opt_ms")
NS = 1e-6  # ms
PUBLISHED = dict(
    hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
    shared_intermediate_size=8192, intermediate_size=8192, vocab_size=100352,
    mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=256,
    attention_multiplier=0.015625, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-05,
    tie_word_embeddings=True, position_embedding_type="nope",
    num_local_experts=0, max_position_embeddings=131072,
    num_hidden_layers=40)
GRANITE = dict(d=2048, d_ff=8192, n_heads=32, n_kv_heads=8, head_dim=64,
               mamba_heads=64, mamba_d_head=64, mamba_d_state=128,
               vocab_rows=100352)


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny_granite")))


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(tiny_root, trace, capsys):
    with open(os.path.join(tiny_root, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        assert json.load(f)["hidden_size"] == 64  # the tiny copy
    result = harness.run_cell("granite-h-t8192", seed=3000000019,
                              seconds=0.2, trace=trace,
                              t_start=time.perf_counter(), root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    said = capsys.readouterr().out
    assert "first-step loss vs float32 reference (the scan as a " \
        "recurrence)" in said
    assert "every token's cross-entropy vs float32 reference" in said
    if trace:
        # No device plane on the CPU: the device metrics are left out.
        assert set(result["metrics"]) == {"host_dispatch_ms"}
    else:
        assert set(result["metrics"]) == {
            "samples_per_s_chip", "step_mem_GiB", "setup_s"}


def test_the_entries_are_the_issues():
    """Held by name, not by place: a later PR appends after them."""
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads", "per_layer")}
    config = by_name["configs"]["granite-4.0-h-micro"]
    assert (config["file"], config["reduced"]) == (
        "benchmark/configs/granite-4.0-h-micro.json", ["num_hidden_layers"])
    cell = by_name["workloads"]["granite-h-t8192"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "t8192-b1", 1)
    for name in NEW:
        metric = by_name["per_layer"][name]
        assert metric["workloads"] == ["granite-h-t8192"]
        assert metric["moves"] == "samples_per_s_chip"
        assert metric["source"] == "device_trace"
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert (reader.LAYER, reader.UNIT) == (metric["layer"],
                                               metric["unit"])
    # The cell reports the four and the seven every cell shares.
    spec = harness.load_cell("granite-h-t8192", tiny.ROOT)
    assert {m["name"] for m in spec["per_layer"]} == set(NEW + SHARED)
    traffic = spec["traffic"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["steps_per_chunk"]) == (1, 8192, 1)


def test_the_configuration_holds_the_published_keys():
    """The catalog row of granite-4.0-h-micro; only the depth is cut,
    and the published ``layer_types`` stays whole: the runner builds its
    first ten, one period of five Mamba layers, attention, four Mamba."""
    from benchmark.runners import decoder_hybrid

    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    kinds = config["layer_types"]
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert decoder_hybrid.layer_types(config) == \
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    for key in ("dtype", "optimizer", "recompute", "deployment",
                "initialisation"):
        assert key in config["assumed"]
    cfg = decoder_hybrid.transformer_config(config)
    assert (cfg.mamba_heads * cfg.mamba_d_head, cfg.d_ff, cfg.kv_heads,
            cfg.tie_embeddings, cfg.pos_table, cfg.rope) == (
        4096, 8192, 8, True, False, False)


# ---- counts by hand ---------------------------------------------------------

def test_model_flops_by_hand():
    # A Mamba mixer: in-projection 2048 x (4096 + 4096 + 2 x 128 + 64) =
    # 17,432,576, out-projection 4096 x 2048 = 8,388,608. The attention
    # mixer: 2048 x 64 x (32 + 2 x 8) = 6,291,456 and 2048 x 2048 =
    # 4,194,304. The MLP 3 x 2048 x 8192 = 50,331,648. The table
    # 2048 x 100,352 = 205,520,896.
    assert flops_ssd.mamba_mixer_matmul_params(2048, 64, 64, 128) == \
        25_821_184
    assert flops_ssd.attention_mixer_matmul_params(2048, 32, 8, 64) == \
        10_485_760
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    params = flops_ssd.hybrid_matmul_params(layer_types=period, **GRANITE)
    assert params == (9 * (25_821_184 + 50_331_648)
                      + 10_485_760 + 50_331_648 + 205_520_896)
    # The scan: 3 x (2 x 256 x 128 + 2 x 256 x 64 x 64 + 4 x 128 x 64 x 64).
    assert flops_ssd.ssd_train_flops_per_token(256, 128, 1, 64, 64) == \
        3 * (65_536 + 2_097_152 + 2_097_152) == 12_779_520
    per_token = flops_ssd.hybrid_train_flops_per_token(
        layer_types=period, mamba_chunk=256, seq_len=8192, **GRANITE)
    assert per_token == (6 * params + 6 * 8192 * 2048 + 9 * 12_779_520)
    assert round(per_token / 1e6) == 5926
    # The head is 21 % of it; 6 % of the full 40 layers'.
    full = flops_ssd.hybrid_train_flops_per_token(
        layer_types=(period * 4), mamba_chunk=256, seq_len=8192, **GRANITE)
    assert round(100 * 6 * 205_520_896 / per_token) == 21
    assert round(100 * 6 * 205_520_896 / full) == 6


def test_scan_operations_and_bytes_by_hand():
    # Tiny: 4 tokens, chunk 2, state 3, one group, 2 heads of 5, bf16.
    # FLOPs a token 3 x (2 x 2 x 3 + 2 x 2 x 5 x 2 + 4 x 3 x 5 x 2) = 516.
    assert flops_ssd.ssd_train_flops(4, 2, 3, 1, 5, 2) == 4 * 516
    # Bytes: four [4, 10] bf16 arrays 320; B, C and their gradients
    # 2 x 2 x 4 x 3 x 2 = 96; dt and its gradient 2 x 4 x 2 x 4 = 64.
    assert flops_ssd.ssd_train_bytes(4, 3, 1, 5, 2, 2) == 320 + 96 + 64
    # At the cell's shapes on a v5e compute bounds it: 0.53 ms of
    # operations against 0.34 ms of bytes a layer.
    ops_ms = 1e3 * flops_ssd.ssd_train_flops(8192, 256, 128, 1, 64, 64) \
        / 197e12
    bytes_ms = 1e3 * flops_ssd.ssd_train_bytes(8192, 128, 1, 64, 64, 2) \
        / 819e9
    assert round(ops_ms, 2) == 0.53 and round(bytes_ms, 2) == 0.34


# ---- the readers by hand ----------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step                   forward, the scan
#   %closed_call.1 200 ns a step                      backward, the scan
#   %all-reduce.1  250 ns, then 300 ns                backward, the MLP
#   %fusion.2   100 ns a step, beside %all-reduce.1   forward, the
#                                                     in-projection
#   %fusion.3   100 ns a step                         forward, the MLP
LAYER = "while/body/closed_call/while/body/closed_call"
HAND_PATHS = {
    "%fusion.1": f"jit(s)/jvp(forward)/{LAYER}/mamba/ssd/checkpoint/"
                 "bchij,bcjhp->bcihp/dot_general",
    "%closed_call.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/mamba/ssd/"
                      "checkpoint/exp",
    "%all-reduce.1": f"jit(s)/transpose(jvp(forward))/{LAYER}/mlp/"
                     "btf,fd->btd/dot_general",
    "%fusion.2": f"jit(s)/jvp(forward)/{LAYER}/mamba/mamba_in_proj/"
                 "btd,dchp->btchp/dot_general",
    "%fusion.3": f"jit(s)/jvp(forward)/{LAYER}/mlp/btd,dcf->btcf/"
                 "dot_general",
}


class _HybridJob:
    model_flops_per_step = 0.0
    gated_mlp = True
    ssd = dict(tokens=4, chunk=2, d_state=3, groups=1, d_head=5, heads=2,
               layers=1, itemsize=2)


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


@pytest.mark.parametrize("metric, want", [
    ("mamba_ms", (290 + 200 + 100) * NS),
    ("ssd_ms", (290 + 200) * NS),
    # 2,064 FLOPs over 1e12 FLOP/s bounds it (480 B over 1e12 B/s is
    # less): 2.064 ns a step of 490
    ("ssd_roofline", 100 * 2.064 / 490),
    ("gated_mlp_ms", (275 + 100) * NS)])
def test_each_new_reader_by_hand(small, metric, want):
    ctx = _scoped(small, HAND_PATHS)
    ctx.job = _HybridJob()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    assert _read(metric, ctx) == pytest.approx(want)


@pytest.mark.parametrize("paths", [
    {},  # the parent's trace, or the CPU's: no path at all
    {"%fusion.1": "jit(step)/jvp(while)/body/dot_general"}])
def test_no_scope_is_none_from_every_new_reader(small, paths):
    ctx = _scoped(small, paths)
    ctx.job = _HybridJob()
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    for metric in NEW:
        assert _read(metric, ctx) is None


def test_a_plain_decoders_job_has_no_gated_mlp_or_roofline(small):
    """``gpt2s``'s ``mlp`` scope is ``mlp_ms``'s to read: a job that
    states neither a gated MLP nor a scan gets neither metric."""
    ctx = _scoped(small, HAND_PATHS)  # ctx.job is the plain decoder's
    ctx.peaks = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e12)
    assert _read("gated_mlp_ms", ctx) is None
    assert _read("ssd_roofline", ctx) is None
    assert _read("mlp_ms", ctx) == pytest.approx(375 * NS)


def test_the_scope_table_by_hand(small):
    """PERF.md's by-scope table of the cell, from the committed tree."""
    from benchmark import scope_table

    ctx = _scoped(small, HAND_PATHS)
    got = scope_table.rows(ctx, ["mamba", "ssd", "mlp"])
    want = [("mamba", 390, 200), ("ssd", 290, 200), ("mlp", 100, 275),
            (scope_table.NO_SCOPE, 0, 0)]
    assert [r[0] for r in got] == [r[0] for r in want]
    for (_, fwd, bwd), (_, want_fwd, want_bwd) in zip(got, want):
        assert (fwd, bwd) == pytest.approx((want_fwd * NS, want_bwd * NS))
    # Without ``mlp`` among the names its time is under none of them.
    assert scope_table.rows(ctx, ["ssd"])[-1][1:] == pytest.approx(
        (200 * NS, 275 * NS))
    lines = scope_table.table(ctx, ["mamba", "ssd", "mlp"], top=1)
    assert lines[0].startswith("2 steps") and len(lines) == 5 + 2 * 3 + 1
    assert lines[5] == "-- longest, mamba" and "%fusion.1" in lines[6]


# ---- the gradient check, at a tiny size -------------------------------------

def test_grad_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    from benchmark import grad_check_hybrid

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    assert grad_check_hybrid.main(["--seed", "7", "--seq-len", "32"]) == 0
    out = capsys.readouterr().out
    assert "float32 m_A_log" in out and "bf16    m_wzx" in out
    assert json.loads(out.splitlines()[-1])["ok"] is True


def test_limit_check_at_a_tiny_size(tiny_root, monkeypatch, capsys):
    """In a float32 program every part that the check runs in bf16 must
    stand out: that is the proof that each patch reaches its part."""
    from benchmark import limit_check_hybrid

    monkeypatch.setattr(harness, "HERE", os.path.join(tiny_root,
                                                      "benchmark"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    limit_check_hybrid.main(["--seed", "7", "--seq-len", "32"])
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    readings = {part: rms for part, (rms,) in report["readings"].items()}
    assert set(readings) == set(limit_check_hybrid.PARTS)
    stated = readings.pop("as stated")
    assert stated < 1e-5
    assert min(readings.values()) > 5 * stated, readings
    assert set(limit_check_hybrid.SEEN) <= set(readings)
