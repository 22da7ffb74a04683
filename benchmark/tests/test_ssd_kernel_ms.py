"""``ssd_kernel_ms`` on the small hand-made trace (tests/data/
small_trace.json, drawn in test_scope_reduce.py) with three of its
instructions renamed here into what a v5e trace calls the scan's kernels:
%fusion.2 (100 ns a step) becomes the forward's Mosaic call, %fusion.3
(100) the backward's, and %closed_call.1 (200) stays the flash kernel it
was. The Mosaic calls under ``ssd`` count; a flash call under
``attention`` and XLA's own instructions under ``ssd`` do not."""
import json
import os

import pytest

from benchmark.layer_metrics import ssd_kernel_ms, ssd_ms
from benchmark.tests import tiny
from benchmark.tests.test_scope_reduce import NS, _scoped, small  # noqa: F401

FWD = "jit(hvd_decoder_step)/jvp(forward)"
BWD = "jit(hvd_decoder_step)/transpose(jvp(forward))"
MAMBA = "while/body/closed_call/while/body/closed_call/mamba"
KERNELS = {
    "%fusion.2": "%ssd_fwd.1 custom-call bf16[1,8192,4096] tpu_custom_call",
    "%fusion.3": "%transpose_jvp_ssd_bwd__.1 custom-call bf16[1,8192,4096] "
                 "tpu_custom_call",
}
# The paths a v5e trace gave these instructions (PERF.md section 5).
PATHS = {
    "%ssd_fwd.1": f"{FWD}/{MAMBA}/ssd/ssd_fwd/pallas_call",
    "%transpose_jvp_ssd_bwd__.1":
        f"{BWD}/{MAMBA}/ssd/transpose(jvp(ssd_bwd))/pallas_call",
    # The states' matmul, XLA's: under ``ssd`` and no kernel.
    "%fusion.1": f"{FWD}/{MAMBA}/ssd/bcjhp,bcjn->bchpn/dot_general",
    "%closed_call.1": f"{BWD}/while/body/closed_call/while/body/closed_call"
                      "/attention/flash_dq/pallas_call",
}


@pytest.fixture
def with_kernels(small):
    """The small trace with the two instructions renamed on every line."""
    def renamed(name):
        head, _, _ = name.partition(" ")
        return KERNELS.get(head, name)

    return {plane: {line: [(renamed(n), s, d) for n, s, d in events]
                    for line, events in lines.items()}
            for plane, lines in small.items()}


def test_the_mosaic_calls_under_ssd_count_and_nothing_else(with_kernels):
    ctx = _scoped(with_kernels, PATHS)
    assert ssd_kernel_ms.read(ctx) == pytest.approx((100 + 100) * NS)
    # ... of a scan that also holds XLA's 290 ns a step.
    assert ssd_ms.read(ctx) == pytest.approx((290 + 100 + 100) * NS)


def test_one_direction_alone_counts(with_kernels):
    paths = dict(PATHS, **{"%ssd_fwd.1": f"{FWD}/{MAMBA}/mamba_conv/mul"})
    assert ssd_kernel_ms.read(_scoped(with_kernels, paths)) == \
        pytest.approx(100 * NS)


@pytest.mark.parametrize("paths", [
    # The einsum form: instructions under ``ssd``, none of them Mosaic's.
    {"%fusion.1": PATHS["%fusion.1"],
     "%closed_call.1": PATHS["%closed_call.1"]},
    # A flash call alone: the kernels of another block.
    {"%closed_call.1": PATHS["%closed_call.1"]},
    # A Mosaic call whose path only resembles the scope.
    {"%ssd_fwd.1": f"{FWD}/{MAMBA}/ssd_fwd/pallas_call"},
])
def test_no_kernel_under_ssd_is_none_and_never_zero(with_kernels, paths):
    assert ssd_kernel_ms.read(_scoped(with_kernels, paths)) is None


def test_no_scoped_events_is_none(with_kernels):
    # The parent's trace, the CPU's rehearsal.
    ctx = _scoped(with_kernels, {})
    assert ctx.scoped_events is None
    assert ssd_kernel_ms.read(ctx) is None


def test_the_entry_is_the_issues():
    """Held by name, not by place: a later PR appends after it."""
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        entries = [e for e in json.load(f)["per_layer"]
                   if e["name"] == "ssd_kernel_ms"]
    assert entries == [dict(
        name="ssd_kernel_ms", unit=ssd_kernel_ms.UNIT, better="lower",
        source="device_trace", layer=ssd_kernel_ms.LAYER,
        moves="samples_per_s_chip", workloads=["granite-h-t8192"])]
