"""The reduction from trace to metrics on a small trace in the names a
v5e trace carries (tests/data/small_trace.json), against answers worked
out by hand, and on a trace recorded on the chip
(tests/data/recorded_dp4_trace.json.gz), against a second, slower way of
computing the same numbers."""
import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(name):
    opener = gzip.open if name.endswith(".gz") else open
    with opener(os.path.join(DATA, name), "rt") as f:
        raw = json.load(f)
    return {plane: {line: [tuple(e) for e in events]
                    for line, events in lines.items()}
            for plane, lines in raw.items()}


class _Job:
    model_flops_per_step = 0.0


def _context(trace, chips):
    return tr.Context(trace=trace, chips=chips, steps=2, dispatch_s=[1e-4],
                      job=_Job(), peaks=None)


# ---- by hand ----------------------------------------------------------------
# Chip 0, two steps of the module jit_step: [1000, 1900] and [2000, 2950],
# so the steady window is [1000, 2950] = 1950 ns.
#   step 1: while 1000-1300 (body 1000-1140, 1150-1300), kernel 1300-1500,
#           all-reduce 1500-1750 with fusion.2 1600-1700 beside it,
#           idle 1750-1800, fusion.3 1800-1900; idle 1900-2000 between steps
#   step 2: while 2000-2300, idle 2300-2350, kernel 2350-2550, all-reduce
#           2550-2850 with fusion.2 2600-2700 beside it, fusion.3 2850-2950

@pytest.fixture(scope="module")
def small():
    return _load("small_trace.json")


def test_steps_are_the_module_that_took_most_time(small):
    ctx = _context(small, 1)
    assert ctx.window == (1000, 2950)
    assert ctx.step_device_ms() == pytest.approx((900 + 950) / 2 / 1e6)


def test_busy_union_and_idle_share_by_hand(small):
    lines = small["/device:TPU:0"]
    # The loop counts as busy between its body's operations (1140-1150).
    assert tr.busy_ns(lines, (1000, 2950)) == 1950 - (50 + 100 + 50)
    assert tr.idle_gaps(lines, (1000, 2950)) == [
        (1750, 1800), (1900, 2000), (2300, 2350)]
    ctx = _context(small, 2)
    # Chip 1 is busy all through its two steps but not between them:
    # busy 1850 of 1950; chip 0 busy 1750 of 1950.
    busy_s, window_s = ctx.busy_and_window_s()
    assert busy_s == pytest.approx((1750 + 1850) / 2 / 1e9)
    assert window_s == pytest.approx(1950 / 1e9)


def test_all_reduce_time_and_its_exposed_part_by_hand(small):
    lines = small["/device:TPU:0"]
    whole, exposed = tr.matching_ns(lines, (1000, 2950), tr.is_all_reduce)
    assert whole == 250 + 300
    # fusion.2 hides 100 ns of each; the asynchronous copy and the loop
    # that contains other operations hide nothing.
    assert exposed == 150 + 200


def test_mosaic_kernel_time_by_hand(small):
    lines = small["/device:TPU:0"]
    whole, _ = tr.matching_ns(lines, (1000, 2950), tr.is_mosaic_kernel)
    assert whole == 200 + 200
    assert _context(small, 1).per_step_ms(whole) == pytest.approx(200 / 1e6)


def test_breakdown_names_leaf_operations_and_gaps_by_host_span(small):
    out = _context(small, 1).breakdown()
    ops = dict(out["device_ops"])
    assert not any(" while " in name for name in ops)
    assert ops["%fusion.1 fusion bf16[8,8]"] == pytest.approx(580 / 1e9)
    assert ops["%all-reduce.1 all-reduce f32[25557032]"] == \
        pytest.approx(550 / 1e9)
    # Longest first: between the steps (the host is in its fence), then
    # the two of 50 ns: 1750-1800 falls in the second dispatch span.
    assert out["idle_gaps"][0] == ["bench.fence", pytest.approx(100 / 1e9)]
    assert sorted(g[0] for g in out["idle_gaps"][1:]) == [
        "bench.dispatch", "bench.fence"]


def test_shorten_reads_name_opcode_shape_and_target():
    kernel = (
        '%closed_call.296 = bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} '
        'custom-call(s32[2]{0:T(128)S(1)} %broadcast.421, '
        'bf16[96,1024,64]{2,1,0:T(8,128)(2,1)} %bitcast.569), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[2]{0}}')
    assert tr.shorten(kernel) == \
        "%closed_call.296 custom-call bf16[96,1024,64] tpu_custom_call"
    assert tr.is_mosaic_kernel(tr.shorten(kernel))
    loop = ('%while.117 = (s32[]{:T(128)}, bf16[8,1024,768]{1,2,0:T(8,128)'
            '(2,1)S(1)}) while((s32[]{:T(128)}, bf16[8,1024,768]{1,2,0}) '
            '%tuple.5), condition=%cond, body=%body')
    assert tr.opcode(tr.shorten(loop)) == "while"
    # A fusion that only reads a kernel's result is not a kernel.
    reader = ('%fusion.9 = f32[8]{0} fusion(bf16[8]{0} %custom-call.3), '
              'kind=kLoop, calls=%fused_computation.9')
    assert tr.opcode(tr.shorten(reader)) == "fusion"
    assert not tr.is_mosaic_kernel(tr.shorten(reader))
    assert tr.shorten("jit_step(123)") == "jit_step(123)"
    assert tr.opcode("jit_step(123)") == ""


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.clip([(0, 5), (6, 9)], (4, 7)) == [(4, 5), (6, 7)]


# ---- recorded on the chip ---------------------------------------------------
# Two steps of chips 0 and 1 of resnet50-dp4 (batch 128 a chip) cut out of
# PR 23's traced run on four TPU v5e chips, times shifted to start near 0.

@pytest.fixture(scope="module")
def recorded():
    return _load("recorded_dp4_trace.json.gz")


def _sweep(events, window):
    """Time covered by at least one event inside ``window``, by counting
    how many events are open at each boundary: a second way to the
    union's length."""
    lo, hi = window
    edges = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    covered, open_, since = 0.0, 0, None
    for at, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if open_ == 0 and step == 1:
            since = at
        open_ += step
        if open_ == 0:
            covered += at - since
    return covered


def test_recorded_trace_is_what_the_docstring_says(recorded):
    lines = recorded["/device:TPU:0"]
    assert set(lines) == {tr.MODULES_LINE, tr.OPS_LINE, tr.ASYNC_LINE}
    steps = tr.step_events(lines)
    assert len(steps) == 2 and steps[0][0].startswith("jit_step_fn(")
    exchanges = [e for e in lines[tr.OPS_LINE] if tr.is_all_reduce(e[0])]
    # One all-reduce a step: the gradients, the batch-norm statistics and
    # the loss packed into one 102 MB tuple by XLA's combiner.
    assert [e[0] for e in exchanges] == [
        "%all-reduce all-reduce f32[25557032]"] * 2


def test_recorded_busy_idle_and_step_time(recorded):
    lines = recorded["/device:TPU:0"]
    ctx = _context(recorded, 2)
    window = ctx.window
    assert window == (893.0, 50577990.0 + 50570593.0)
    assert ctx.step_device_ms() == pytest.approx(50.570768)
    busy = tr.busy_ns(lines, window)
    assert busy == pytest.approx(_sweep(lines[tr.OPS_LINE], window))
    idle = tr.idle_gaps(lines, window)
    assert busy + tr.total(idle) == pytest.approx(window[1] - window[0])
    # The chip waits for nothing: the only gap of note is the 6 us between
    # the two programs.
    assert 0 < 1 - busy / (window[1] - window[0]) < 1e-3
    busy_s, window_s = ctx.busy_and_window_s()
    assert 0.999 < busy_s / window_s < 1


def test_recorded_all_reduce_is_wholly_exposed(recorded):
    lines = recorded["/device:TPU:0"]
    window = tr.steady_window(lines)
    whole, exposed = tr.matching_ns(lines, window, tr.is_all_reduce)
    assert whole == 1785699.0 + 1788151.0
    others = [e for e in lines[tr.OPS_LINE] if not tr.is_all_reduce(e[0])]
    both = _sweep(lines[tr.OPS_LINE], window)
    assert exposed == pytest.approx(both - _sweep(others, window))
    # Nothing runs on the core beside it: the exchange comes after the
    # last gradient and before the update.
    assert exposed == whole
