"""The reduction from scope paths to per-layer metrics: the classifier on
hand-written paths, every reader on a small trace against sums done by
hand (tests/data/small_trace.json with paths written here), the bytes of
an ``.xplane.pb`` that jax's own encoder wrote, and two steps of
``gpt2s-t128`` and of ``resnet50-dp4`` cut from PR 24's traced runs on
the chip (tests/data/recorded_scopes.json.gz: the names and times
``trace_reduce.load`` gives, and each instruction's scope path)."""
import gzip
import importlib
import json
import os

import pytest

from benchmark import scope_reduce as sr
from benchmark import trace_reduce as tr
from benchmark.tests import tiny
from benchmark.tests.test_trace_reduce import DATA, _context, _load

STEP = "jit(hvd_decoder_step)"
LAYER = "while/body/closed_call/while/body/closed_call"


@pytest.mark.parametrize("path, want", [
    (f"{STEP}/jvp(forward)/{LAYER}/mlp/btd,df->btf/dot_general", sr.FORWARD),
    (f"{STEP}/transpose(jvp(forward))/{LAYER}/mlp/btf,fd->btd/dot_general",
     sr.BACKWARD),
    ("jit(hvd_dp_step)/shard_map/jvp(forward)/ResNet/Conv_0/conv_general",
     sr.FORWARD),
    # Rematerialised work belongs to the pass that runs it.
    (f"{STEP}/transpose(jvp(forward))/{LAYER}/checkpoint/"
     "rematted_computation/mlp/dot_general", sr.BACKWARD),
    # exchange and optimizer first, wherever they are.
    ("jit(hvd_dp_step)/exchange/psum", sr.EXCHANGE),
    ("jit(hvd_dp_step)/optimizer/exchange/psum", sr.EXCHANGE),
    ("jit(hvd_dp_step)/optimizer/add", sr.OPTIMIZER),
    ("jit(hvd_dp_step)/cond/branch_1_fun/optimizer/mul", sr.OPTIMIZER),
    # Of joined names the first counts.
    (f"{STEP}/optimizer/add;{STEP}/transpose(jvp(forward))/head/dot_general",
     sr.OPTIMIZER),
    (f"{STEP}/jvp(forward)/head/mul;{STEP}/optimizer/add", sr.FORWARD),
    # No scope of the vocabulary: the parent's programs, the compiler's own.
    ("jit(step_fn)/jvp(ResNet)/Conv_0/conv_general_dilated", sr.UNSCOPED),
    ("jit(step)/jvp(jit(log_softmax))/forwarded/sub", sr.UNSCOPED),
    ("", sr.UNSCOPED),
])
def test_classify(path, want):
    assert sr.classify(path) == want


def test_scope_path_is_the_op_name_of_xprofs_tf_op():
    assert sr.scope_path("jit(s)/mlp/btd,df->btf/dot_general:") == \
        "jit(s)/mlp/btd,df->btf/dot_general"
    assert sr.scope_path("jit(s)/mlp/add:Add") == "jit(s)/mlp/add"
    assert sr.scope_path("jit(s)/mlp/add") == "jit(s)/mlp/add"


# ---- by hand ----------------------------------------------------------------
# small_trace.json, chip 0, two steps (test_trace_reduce.py draws them):
#   %fusion.1   140 + 150 ns a step, in the loop      forward, mlp
#   %closed_call.1 (a Mosaic call) 200 ns a step      backward, flash_dq
#   %all-reduce.1  250 ns, then 300 ns                exchange
#   %fusion.2   100 ns a step, beside the all-reduce  backward, head
#   %fusion.3   100 ns a step                         optimizer
#   %while.1    a container: not a leaf
HAND_PATHS = {
    "%fusion.1": "jit(s)/jvp(forward)/while/body/closed_call/mlp/dot_general",
    "%closed_call.1": "jit(s)/transpose(jvp(forward))/while/body/closed_call"
                      "/attention/flash_dq/pallas_call",
    "%all-reduce.1": "jit(s)/exchange/psum",
    "%fusion.2": "jit(s)/transpose(jvp(forward))/head/dot_general",
    "%fusion.3": "jit(s)/optimizer/add",
}
NS = 1e-6  # ms


def _scoped(trace, paths, chips=1):
    ctx = _context(trace, chips)
    ctx.scoped_events = sr.attach(ctx, paths)
    return ctx


@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


def test_classes_by_hand(small):
    ctx = _scoped(small, HAND_PATHS)
    assert sr.class_ms(ctx, sr.FORWARD) == pytest.approx(290 * NS)
    assert sr.class_ms(ctx, sr.BACKWARD) == pytest.approx(300 * NS)
    assert sr.class_ms(ctx, sr.EXCHANGE) == pytest.approx(275 * NS)
    assert sr.class_ms(ctx, sr.OPTIMIZER) == pytest.approx(100 * NS)
    assert sr.class_ms(ctx, sr.UNSCOPED) is None  # nothing: not a zero


@pytest.mark.parametrize("metric, want", [
    ("fwd_ms", 290), ("bwd_ms", 300), ("opt_ms", 100),
    ("head_loss_ms", 100), ("mlp_ms", 290), ("flash_dq_ms", 200),
    ("flash_fwd_ms", None), ("flash_dkv_ms", None)])
def test_each_reader_by_hand(small, metric, want):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    value = reader.read(_scoped(small, HAND_PATHS))
    assert value == (pytest.approx(want * NS) if want else None)


def test_a_union_not_a_sum_where_events_overlap(small):
    """%fusion.2 runs beside the all-reduce: in one class the time they
    share counts once (250 + 300 ns, not 450 + 500)."""
    ctx = _scoped(small, dict(HAND_PATHS, **{
        "%fusion.2": "jit(s)/exchange/concatenate"}))
    assert sr.class_ms(ctx, sr.EXCHANGE) == pytest.approx(275 * NS)


def test_a_kernel_is_a_mosaic_call_under_the_scope(small):
    """An XLA fusion under ``flash_dq`` (a twin's, or a layout copy) is
    not the kernel; a Mosaic call under ``flash_xla`` is not either."""
    ctx = _scoped(small, dict(HAND_PATHS, **{
        "%fusion.2": "jit(s)/transpose(jvp(forward))/flash_dq/mul"}))
    assert sr.kernel_ms(ctx, "flash_dq") == pytest.approx(200 * NS)
    assert sr.scope_ms(ctx, "flash_dq") == pytest.approx(300 * NS)
    assert sr.kernel_ms(ctx, "flash_xla") is None


@pytest.mark.parametrize("paths", [
    {},  # the parent's trace read with these files: no path at all
    {"%fusion.1": "jit(step)/jvp(while)/body/dot_general",
     "%fusion.3": "jit(step)/add"},  # paths, none of the vocabulary
])
def test_no_scope_is_none_from_every_reader(small, paths):
    ctx = _scoped(small, paths)
    assert ctx.scoped_events is None
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        new = [m["name"] for m in json.load(f)["per_layer"]][8:]
    assert len(new) == 8
    for metric in new:
        reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
        assert reader.read(ctx) is None


def test_no_device_plane_is_none_and_reads_no_file(small, monkeypatch):
    """The CPU's rehearsal: nothing is looked for, whatever trace an
    earlier run left under the checkout."""
    host_only = {tr.HOST_PLANE: small[tr.HOST_PLANE]}
    monkeypatch.setattr(sr, "metadata_stat", None)  # would raise if called
    ctx = _context(host_only, 1)
    assert sr.scoped_events(ctx) is None
    assert sr.class_ms(ctx, sr.FORWARD) is None


# ---- the file's bytes -------------------------------------------------------

XSPACE = '''
planes { name: "/device:TPU:1"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()"
    stats { metadata_id: 2 str_value: "other/chip:" } } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } } }
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 140000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%fused_computation.1"
    display_name: "fusion.1"
    stats { metadata_id: 3 ref_value: 4 }
    stats { metadata_id: 5 uint64_value: 5889827440982395914 }
    stats { metadata_id: 2 str_value: "jit(s)/jvp(forward)/while/body/closed_call/mlp/btd,df->btf/dot_general:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%copy-done.62 = bf16[768,50304]{1,0} copy-done((bf16[768,50304]{1,0}, bf16[768,50304]{0,1}, u32[]{:S(2)}) %copy-start.62)"
    stats { metadata_id: 3 ref_value: 6 } } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"
    stats { metadata_id: 2 ref_value: 7 } } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
  stat_metadata { key: 4 value { id: 4 name: "convolution fusion" } }
  stat_metadata { key: 5 value { id: 5 name: "program_id" } }
  stat_metadata { key: 6 value { id: 6 name: "copy-done" } }
  stat_metadata { key: 7 value { id: 7 name: "jit(s)/optimizer/add:" } } }
'''


def test_metadata_stats_from_the_bytes_jax_writes(tmp_path, small):
    """An XSpace in the shape a v5e trace has (the stat on the event's
    metadata, as a string or as a reference to a stat's name), encoded
    by jax's own library, read back by the decoder here; then joined to
    a trace's events through the file."""
    from jax.profiler import ProfileData

    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert sr.metadata_stat(path, "/device:TPU:0") == {
        "%fusion.1": "jit(s)/jvp(forward)/while/body/closed_call/mlp/"
                     "btd,df->btf/dot_general:",
        "%fusion.3": "jit(s)/optimizer/add:"}
    assert sr.metadata_stat(path, "/device:TPU:0", "hlo_category") == {
        "%fusion.1": "convolution fusion", "%copy-done.62": "copy-done"}
    assert sr.metadata_stat(path, "/device:TPU:0", "program_id") == {
        "%fusion.1": 5889827440982395914}
    assert sr.metadata_stat(path, "/device:TPU:2") == {}
    ctx = _context(small, 1)
    events = sr.scoped_events(ctx, path)
    assert ctx.scoped_events is events  # parsed once, kept on the ctx
    assert sr.class_ms(ctx, sr.FORWARD) == pytest.approx(290 * NS)
    assert sr.class_ms(ctx, sr.OPTIMIZER) == pytest.approx(100 * NS)
    # The kernel and the all-reduce; %fusion.2 runs inside the latter.
    assert sr.class_ms(ctx, sr.UNSCOPED) == pytest.approx((200 + 275) * NS)


# ---- recorded on the chip ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "recorded_scopes.json.gz"), "rt") as f:
        raw = json.load(f)
    cells = {}
    for cell, data in raw.items():
        trace = {plane: {line: [tuple(e) for e in events]
                         for line, events in lines.items()}
                 for plane, lines in data["trace"].items()}
        cells[cell] = _scoped(trace, data["paths"])
    return cells


def _sum_ms(ctx, keep):
    """Sum of the leaf events' durations that ``keep(name, path)`` takes,
    per step: the core runs one instruction at a time, so on a recorded
    trace the union's length is the plain sum — the slower second way."""
    leaves = [e for e in ctx.lines[tr.OPS_LINE]
              if not any(f" {c} " in e[0] for c in tr.CONTAINERS)]
    paths = {name: path for name, _, _, path in ctx.scoped_events}
    return sum(dur for name, _, dur in leaves
               if keep(name, paths[name])) / 2 / 1e6


@pytest.mark.parametrize("cell", ["gpt2s-t128", "resnet50-dp4"])
def test_recorded_classes_are_disjoint_and_cover_the_step(recorded, cell):
    ctx = recorded[cell]
    assert len(tr.step_events(ctx.lines)) == 2
    step_ms = ctx.step_device_ms()
    parts = {c: sr.class_ms(ctx, c) or 0.0
             for c in (sr.FORWARD, sr.BACKWARD, sr.OPTIMIZER, sr.EXCHANGE)}
    assert parts[sr.FORWARD] > 0 and parts[sr.BACKWARD] > parts[sr.FORWARD]
    assert parts[sr.OPTIMIZER] > 0
    # The four classes are most of the step (92 % on four chips, where
    # the compiler's own copies around the exchange are 3.9 ms) ...
    assert 0.92 * step_ms <= sum(parts.values()) <= 1.005 * step_ms
    assert sr.class_ms(ctx, sr.FORWARD) == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/jvp(forward)/" in p + "/"
        and "/optimizer/" not in p and "/exchange/" not in p))
    assert sr.class_ms(ctx, sr.BACKWARD) == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/transpose(jvp(forward))/" in p + "/"))
    unscoped = sr.class_ms(ctx, sr.UNSCOPED)
    assert unscoped == pytest.approx(_sum_ms(
        ctx, lambda n, p: "forward" not in p and "/optimizer/" not in p
        and "/exchange/" not in p))
    # ... and with the unscoped remainder all of it but the loops' own
    # time between two operations of their bodies.
    assert 0.999 * step_ms <= sum(parts.values()) + unscoped <= step_ms


def test_recorded_decoder_blocks_and_kernels(recorded):
    ctx = recorded["gpt2s-t128"]
    read = {m: importlib.import_module(f"benchmark.layer_metrics.{m}").read(
        ctx) for m in ("head_loss_ms", "mlp_ms", "flash_fwd_ms",
                       "flash_dq_ms", "flash_dkv_ms", "flash_attn_ms")}
    assert read["mlp_ms"] == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/mlp/" in p))
    assert read["head_loss_ms"] == pytest.approx(_sum_ms(
        ctx, lambda n, p: "/head/" in p or "/loss/" in p + "/"))
    # Each kernel is its instruction's name too (%flash_dq.10): the two
    # ways to tell them apart agree, twelve layers each a step, and the
    # three are all the Mosaic calls there are.
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        mine = [e for e in ctx.scoped_events
                if kernel in sr.segments(e[3])
                and tr.is_mosaic_kernel(e[0])]
        assert len(mine) == 2 * 12
        assert all(e[0].startswith(f"%{kernel}.") for e in mine)
        assert read[f"{kernel}_ms"] == pytest.approx(
            sum(e[2] for e in mine) / 2 / 1e6)
    assert read["flash_fwd_ms"] + read["flash_dq_ms"] + \
        read["flash_dkv_ms"] == pytest.approx(read["flash_attn_ms"])
    assert sr.scope_ms(ctx, "flash_xla") is None  # no fall-back
    assert sr.class_ms(ctx, sr.EXCHANGE) is None  # one chip


def test_recorded_all_reduce_sits_under_exchange(recorded):
    ctx = recorded["resnet50-dp4"]
    exchanges = [e for e in ctx.scoped_events if tr.is_all_reduce(e[0])]
    assert len(exchanges) == 2  # one a step
    assert all(sr.classify(e[3]) == sr.EXCHANGE for e in exchanges)
    whole, _ = tr.matching_ns(ctx.lines, ctx.window, tr.is_all_reduce)
    assert sr.class_ms(ctx, sr.EXCHANGE) >= whole / 2 / 1e6
    # The decoder's scopes are not in this program.
    assert sr.scope_ms(ctx, "mlp") is None
    assert sr.kernel_ms(ctx, "flash_fwd") is None
