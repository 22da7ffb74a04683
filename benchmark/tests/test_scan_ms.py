"""``scan_ms`` on the small hand-made trace (tests/data/small_trace.json,
drawn in test_scope_reduce.py: %fusion.1 140 + 150 ns a step, the Mosaic
call %closed_call.1 200, %fusion.2 and %fusion.3 100 each, %all-reduce.1
250 then 300) with paths written here: the layer scan's slices and stacks
count, the same endings inside a block do not."""
import pytest

from benchmark.layer_metrics import scan_ms
from benchmark.tests.test_scope_reduce import NS, _scoped, small  # noqa: F401

FWD = "jit(hvd_decoder_step)/jvp(forward)"
BWD = "jit(hvd_decoder_step)/transpose(jvp(forward))"
LAYERS = "while/body/closed_call/while/body"
# The names a v5e trace gave these instructions (PERF.md section 5).
SCAN = {
    "%fusion.1": f"{FWD}/{LAYERS}/dynamic_update_slice",  # residuals stacked
    "%closed_call.1": f"{BWD}/{LAYERS}/squeeze",  # a layer's weights
    "%fusion.2": f"{BWD}/{LAYERS}/dynamic_slice",  # residuals sliced
}
ELSEWHERE = {
    "%fusion.3": f"{FWD}/{LAYERS}/closed_call/moe/moe_experts/moe_gmm/"
                 "jit(gmm)/jit(_roll_dynamic)/dynamic_slice",
    "%all-reduce.1": f"{BWD}/{LAYERS}/closed_call/mlp/dynamic_update_slice",
}


def test_the_scans_slices_and_stacks_count_in_both_directions(small):
    ctx = _scoped(small, dict(SCAN, **ELSEWHERE))
    assert scan_ms.read(ctx) == pytest.approx((290 + 200 + 100) * NS)


@pytest.mark.parametrize("path", [
    # The same endings under a block are the block's.
    f"{FWD}/{LAYERS}/closed_call/moe/moe_dispatch/dynamic_slice",
    f"{BWD}/{LAYERS}/closed_call/mlp/dynamic_update_slice",
    f"{FWD}/{LAYERS}/closed_call/attention/squeeze",
    f"{FWD}/embed/dynamic_slice",
    f"{BWD}/head/while/body/squeeze",
    f"{BWD}/loss/dynamic_update_slice",
    # A squeeze outside a loop, other work of the loop, the optimizer's
    # slices and a path without the scope are not the scan's.
    f"{BWD}/squeeze",
    f"{FWD}/{LAYERS}/add",
    "jit(hvd_decoder_step)/optimizer/dynamic_slice",
    "jit(step)/jvp(while)/body/dynamic_update_slice",
])
def test_what_is_not_the_scans(small, path):
    ctx = _scoped(small, dict(SCAN, **{"%fusion.1": path}))
    assert scan_ms.read(ctx) == pytest.approx((200 + 100) * NS)


@pytest.mark.parametrize("path", [
    f"{FWD}/{LAYERS}/squeeze", f"{FWD}/{LAYERS}/dynamic_slice",
    f"{BWD}/{LAYERS}/dynamic_update_slice",
    f"{FWD}/while/body/dynamic_update_slice",  # the pipeline's own scan
    f"{BWD}/{LAYERS}/dynamic_slice;{BWD}/{LAYERS}/closed_call/moe/add",
])
def test_each_ending_counts_alone(small, path):
    ctx = _scoped(small, dict(ELSEWHERE, **{"%fusion.1": path}))
    assert scan_ms.read(ctx) == pytest.approx(290 * NS)


def test_nothing_of_the_scan_is_none_and_never_zero(small):
    # Scoped events, none of them the scan's: a model without a layer scan.
    assert scan_ms.read(_scoped(small, ELSEWHERE)) is None
    # No scoped events at all: the parent's trace, the CPU's rehearsal.
    ctx = _scoped(small, {})
    assert ctx.scoped_events is None
    assert scan_ms.read(ctx) is None
    ctx = _scoped(small, {"%fusion.1": "jit(step)/jvp(while)/body/squeeze"})
    assert ctx.scoped_events is None
    assert scan_ms.read(ctx) is None


def test_the_entry_is_the_issues():
    import json
    import os

    from benchmark.tests import tiny

    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == dict(
        name="scan_ms", unit=scan_ms.UNIT, better="lower",
        source="device_trace", layer=scan_ms.LAYER,
        moves="samples_per_s_chip",
        workloads=["gpt2s-t1024", "gpt2s-t128", "olmoe-t4096"])
