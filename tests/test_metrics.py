"""Unified metrics plane (csrc/hvd/metrics.{h,cc} + common/metrics.py;
docs/metrics.md).

THE acceptance pair:

- **Straggler attribution, deterministically**: a ``kind=delay_ms``
  fault on one rank of a 4-rank world produces STRAGGLER_WARNINGs
  naming exactly that rank, with the per-step rank-skew histogram in
  ``hvd.metrics()`` showing the injected lag.
- **Byte-identical default**: with ``HOROVOD_METRICS_EXPORT`` unset no
  exporter thread starts, no file appears, and the timeline carries no
  counter ("C") events — regression-tested against a run with the knob
  set.

Also here: the snapshot consistency invariant (``bytes_sent == local +
cross + shm`` asserted from ONE snapshot document, not ad-hoc getters),
the log2-percentile math, the Prometheus textfile format, the
STRAGGLER_WARNING timeline-instant emission, and the pinned empty-safe
return shapes of ``hvd.stall_report()`` / ``hvd.liveness_report()`` /
``hvd.metrics()`` when the native plane is absent.
"""

import json
import os
import textwrap

import numpy as np
import pytest

from proc_harness import run_world

import horovod_tpu.common.metrics as hmetrics
from horovod_tpu.common.metrics import (
    percentiles,
    prometheus_text,
    report_text,
)


# ---------------------------------------------------------------------------
# empty-safe shapes (the stall/liveness fix satellite)
# ---------------------------------------------------------------------------


def test_report_shapes_without_native_are_pinned():
    """``hvd.stall_report()`` and ``hvd.liveness_report()`` return the
    EMPTY STRING — not None, not an exception — when nothing is
    initialized / the native core is absent, and ``hvd.metrics()``
    returns its two-key dict with ``native=None``. These shapes are the
    documented contract (docs/metrics.md, docs/liveness.md); monitoring
    code string-concatenates them unconditionally."""
    import horovod_tpu as hvd

    assert not hvd.is_initialized()
    assert hvd.stall_report() == ""
    assert isinstance(hvd.stall_report(), str)
    assert hvd.liveness_report() == ""
    assert isinstance(hvd.liveness_report(), str)
    m = hvd.metrics()
    assert set(m) == {"python", "native", "spans"}
    assert m["native"] is None
    assert isinstance(m["python"], dict)
    assert isinstance(hvd.metrics_report(), str)
    assert "native core: absent" in hvd.metrics_report()


def test_torch_binding_reexports_metrics():
    import horovod_tpu
    import horovod_tpu.torch as hvd_torch

    assert hvd_torch.metrics is horovod_tpu.metrics
    assert hvd_torch.metrics_report is horovod_tpu.metrics_report


# ---------------------------------------------------------------------------
# histogram math + exporter format units
# ---------------------------------------------------------------------------


def test_percentiles_from_log2_buckets():
    # 10 values in bucket 3 (8..15), 10 in bucket 6 (64..127):
    # p50 falls in the first bucket (upper bound 16), p99 in the second
    # (upper bound 128).
    h = {"count": 20, "buckets": [[3, 10], [6, 10]]}
    p = percentiles(h, (50, 99))
    assert p == {"p50": 16, "p99": 128}
    assert percentiles({"count": 0, "buckets": []}) == {
        "p50": 0, "p90": 0, "p99": 0}


def test_prometheus_text_format():
    snap = {
        "python": {"retrier.retries": 2},
        "native": {
            "counters": {"bytes_sent": 123, "cache_hits": 4},
            "histograms": {
                "cycle_us": {"count": 3, "sum": 30, "max": 20,
                             "buckets": [[2, 1], [4, 2]]},
            },
            "straggler": {"warnings": 1, "last_rank": 2,
                          "last_lag_ms": 250.0, "events": []},
        },
    }
    text = prometheus_text(snap)
    assert "# TYPE hvd_retrier_retries counter" in text
    assert "hvd_retrier_retries 2" in text
    assert "hvd_bytes_sent 123" in text
    assert "# TYPE hvd_cycle_us histogram" in text
    # log2 bucket upper bounds, cumulative counts, then +Inf == count.
    assert 'hvd_cycle_us_bucket{le="8"} 1' in text
    assert 'hvd_cycle_us_bucket{le="32"} 3' in text
    assert 'hvd_cycle_us_bucket{le="+Inf"} 3' in text
    assert "hvd_cycle_us_sum 30" in text
    assert "hvd_cycle_us_count 3" in text
    assert "hvd_straggler_warnings 1" in text
    assert "hvd_straggler_last_rank 2" in text


def test_report_text_renders_histograms():
    snap = {
        "python": {"faults.injected": 1},
        "native": {
            "counters": {"cycles": 7},
            "histograms": {
                "gather_wait_us": {"count": 4, "sum": 40, "max": 16,
                                   "buckets": [[3, 4]]},
                "empty_us": {"count": 0, "sum": 0, "max": 0,
                             "buckets": []},
            },
            "straggler": {"warnings": 0, "last_rank": -1,
                          "last_lag_ms": 0.0},
        },
    }
    text = report_text(snap)
    assert "faults.injected: 1" in text
    assert "cycles: 7" in text
    assert "gather_wait_us: n=4" in text
    assert "empty_us" not in text  # empty histograms are noise
    assert "straggler: warnings=0" in text


def test_straggler_events_become_timeline_instants(tmp_path,
                                                   monkeypatch):
    """Drained straggler events are mirrored as STRAGGLER_WARNING
    instants into the active timeline — the name comes from the
    INSTANT_CATALOG constant, args carry rank + lag."""
    import horovod_tpu.common.timeline as timeline_mod
    from horovod_tpu.common.timeline import Timeline

    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    monkeypatch.setattr(hmetrics, "_active_timeline", lambda: tl)
    hmetrics._emit_straggler_instants(
        {"straggler": {"events": [{"rank": 1, "lag_ms": 250.0}]}})
    tl.close()
    events = json.load(open(path))
    hits = [e for e in events
            if e.get("name") == timeline_mod.STRAGGLER_WARNING]
    assert len(hits) == 1
    assert hits[0]["ph"] == "i"
    assert hits[0]["args"] == {"rank": 1, "lag_ms": 250.0}
    assert timeline_mod.STRAGGLER_WARNING in timeline_mod.INSTANT_CATALOG


# ---------------------------------------------------------------------------
# single-process native plane: histograms populate; exporter A/B
# ---------------------------------------------------------------------------


def test_native_snapshot_populates_latency_histograms(monkeypatch):
    import horovod_tpu as hvd

    hvd.init()
    try:
        xs = [np.ones((16,), np.float32) for _ in range(hvd.size())]
        hvd.allreduce(xs, name="metrics.ar")
        m = hvd.metrics()
        native = m["native"]
        if native is None:
            pytest.skip("native core unavailable in this build")
        assert native["counters"]["cycles"] > 0
        h = native["histograms"]
        assert h["enq_to_neg_allreduce_us"]["count"] >= 1
        assert h["neg_to_done_allreduce_us"]["count"] >= 1
        assert h["cycle_us"]["count"] > 0
        # count == sum over buckets (the sparse pairs are complete)
        for name in ("enq_to_neg_allreduce_us", "cycle_us"):
            assert sum(c for _, c in h[name]["buckets"]) == \
                h[name]["count"], name
        # the re-routed consumers agree with the snapshot
        assert hvd.ring_traffic()["bytes_sent"] == \
            native["counters"]["bytes_sent"]
        # liveness_report rides the snapshot drain path: empty-but-str
        # on a healthy world
        assert hvd.liveness_report() == ""
        # a second read is cumulative, not consumed
        again = hvd.metrics()["native"]
        assert again["histograms"]["cycle_us"]["count"] >= \
            h["cycle_us"]["count"]
    finally:
        hvd.shutdown()


def test_exporter_off_is_byte_identical(tmp_path, monkeypatch):
    """HOROVOD_METRICS_EXPORT unset (the default): no pump thread, no
    textfile, and the timeline JSON contains zero counter ("C" phase)
    events — the pre-metrics timeline, byte-for-byte in event kinds."""
    import horovod_tpu as hvd

    tl_path = str(tmp_path / "off.json")
    monkeypatch.setenv("HOROVOD_TIMELINE", tl_path)
    monkeypatch.delenv("HOROVOD_METRICS_EXPORT", raising=False)
    hvd.init()
    try:
        assert hmetrics._pump is None
        hvd.allreduce([np.ones((8,), np.float32)
                       for _ in range(hvd.size())], name="off.ar")
    finally:
        hvd.shutdown()
    events = json.load(open(tl_path))
    assert [e for e in events if e.get("ph") == "C"] == []
    assert list(tmp_path.glob("*.prom")) == []


def test_exporter_writes_textfile_and_timeline_counters(tmp_path,
                                                        monkeypatch):
    import horovod_tpu as hvd

    tl_path = str(tmp_path / "on.json")
    prom_path = str(tmp_path / "metrics.prom")
    monkeypatch.setenv("HOROVOD_TIMELINE", tl_path)
    monkeypatch.setenv("HOROVOD_METRICS_EXPORT", prom_path)
    monkeypatch.setenv("HOROVOD_METRICS_INTERVAL_MS", "60000")
    hvd.init()
    try:
        assert hmetrics._pump is not None
        hvd.allreduce([np.ones((8,), np.float32)
                       for _ in range(hvd.size())], name="on.ar")
        # Deterministic publish (the interval above keeps the thread's
        # own timer out of the test).
        hmetrics._pump.publish_once()
    finally:
        hvd.shutdown()  # stop_pump flushes one final snapshot
    assert hmetrics._pump is None
    text = open(prom_path).read()
    assert "# TYPE hvd_cycle_us histogram" in text
    assert "hvd_cycles" in text
    assert 'le="+Inf"' in text
    events = json.load(open(tl_path))
    counters = [e for e in events if e.get("ph") == "C"]
    assert counters, "exporter should emit timeline counter events"
    names = {e["name"] for e in counters}
    assert {"hvd_bytes", "hvd_control"} <= names
    args = [e["args"] for e in counters if e["name"] == "hvd_control"]
    assert all(set(a) == {"cache_hits", "cycles", "pending"}
               for a in args)


# ---------------------------------------------------------------------------
# consistency invariant from ONE snapshot (4-rank hier+shm world)
# ---------------------------------------------------------------------------

_CONSISTENCY_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    rank = int(sys.argv[1]); port = int(sys.argv[2])
    os.environ.update(HOROVOD_RANK=str(rank), HOROVOD_SIZE="4",
                      HOROVOD_LOCAL_RANK=str(rank // 2),
                      HOROVOD_LOCAL_SIZE="2",
                      HOROVOD_CROSS_RANK=str(rank % 2),
                      HOROVOD_CROSS_SIZE="2",
                      HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                      HOROVOD_CONTROLLER_PORT=str(port),
                      HOROVOD_CYCLE_TIME="1.0",
                      HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                      HOROVOD_HIERARCHICAL_ALLGATHER="1",
                      HOROVOD_SHM="1",
                      JAX_PLATFORMS="cpu")
    from horovod_tpu.common.host_world import world
    from horovod_tpu.common import metrics as hmetrics

    w = world()
    w.init()
    for i in range(3):
        out = w.allgather_np(np.full(2048, float(rank), np.float32),
                             f"cons.{i}")
        assert out.shape == (4, 2048), out.shape
    out = w.broadcast_np(np.arange(512, dtype=np.float32), 0, "cons.b")
    # Quiesce: all waits returned on every rank; give in-flight counter
    # pairs (bytes_sent then local/cross inside AddSent) a beat.
    time.sleep(0.3)
    snap = hmetrics.snapshot()
    c = snap["native"]["counters"]
    assert c["initialized"] == 1 and c["size"] == 4, c
    # THE invariant, from one snapshot document — not ad-hoc getters:
    # every payload byte is exactly one of local-TCP, cross-TCP, or shm.
    assert c["bytes_sent"] == (c["local_bytes"] + c["cross_bytes"]
                               + c["shm_bytes"]), c
    assert c["bytes_sent"] > 0, c
    assert c["shm_active"] == 1 and c["shm_bytes"] > 0, c
    h = snap["native"]["histograms"]
    assert h["enq_to_neg_allgather_us"]["count"] >= 3, h
    assert h["shm_leg_us"]["count"] > 0, h
    if rank == 0:
        # The coordinator's gather-wait histogram saw one entry per
        # worker frame per cycle.
        assert h["gather_wait_us"]["count"] >= 3, h
    w.shutdown()
    print(f"METCONS_{rank}_OK")
""")


def test_snapshot_consistency_invariant_4rank(tmp_path):
    """bytes_sent == local + cross + shm asserted from the unified
    snapshot on every rank of a 2x2 hier world with shm active, plus
    populated gather-wait / shm-leg histograms."""
    run_world(tmp_path, _CONSISTENCY_WORKER, "METCONS", size=4,
              timeout=240)


# ---------------------------------------------------------------------------
# THE straggler acceptance world
# ---------------------------------------------------------------------------

_STRAGGLER_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    rank = int(sys.argv[1]); port = int(sys.argv[2])
    os.environ.update(HOROVOD_RANK=str(rank), HOROVOD_SIZE="4",
                      HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                      HOROVOD_CONTROLLER_PORT=str(port),
                      HOROVOD_CYCLE_TIME="1.0",
                      JAX_PLATFORMS="cpu")
    # Rank 1 stalls 250 ms before EVERY submit: deterministically the
    # last rank of every ready group, far over the 100 ms default
    # threshold (times unlimited — no step pin).
    os.environ["HOROVOD_FAULT_SPEC"] = \\
        "host_world.enqueue:rank=1:kind=delay_ms:ms=250"
    from horovod_tpu.common.host_world import world
    from horovod_tpu.common import metrics as hmetrics

    w = world()
    w.init()
    for i in range(6):
        w.allgather_np(np.asarray([float(rank)], np.float32),
                       f"strag.{i}")
    snap = hmetrics.snapshot()  # == hvd.metrics() (same implementation)
    if rank == 0:
        st = snap["native"]["straggler"]
        # STRAGGLER_WARNING fired, naming EXACTLY the delayed rank.
        assert st["warnings"] >= 1, st
        assert st["last_rank"] == 1, st
        assert all(ev["rank"] == 1 for ev in st["events"]), st
        assert st["last_lag_ms"] >= 100.0, st
        # rank 1's EWMA lag dominates every other rank's.
        ewma = st["ewma_ms"]
        assert ewma[1] == max(ewma) and ewma[1] >= 100.0, ewma
        # The skew histogram shows the injected ~250 ms spread.
        skew = snap["native"]["histograms"]["rank_skew_us"]
        assert skew["count"] >= 3, skew
        assert skew["max"] >= 150_000, skew
    if rank == 1:
        # The python-plane counter saw the injections.
        assert snap["python"].get("faults.injected", 0) >= 3, \\
            snap["python"]
    w.shutdown()
    print(f"STRAG_{rank}_OK")
""")


def test_straggler_attribution_names_the_delayed_rank(tmp_path):
    """THE acceptance run (ISSUE 12): a kind=delay_ms fault on rank 1
    of a 4-rank world produces STRAGGLER_WARNINGs naming exactly rank 1
    (coordinator-side EWMA detector over per-rank ready timestamps),
    and the rank-skew histogram in hvd.metrics() shows the injected
    spread."""
    run_world(tmp_path, _STRAGGLER_WORKER, "STRAG", size=4, timeout=240)


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------


def test_metrics_knobs_parse(monkeypatch):
    from horovod_tpu.common import config as _config

    monkeypatch.delenv("HOROVOD_METRICS_EXPORT", raising=False)
    assert _config.metrics_export_path() is None
    monkeypatch.setenv("HOROVOD_METRICS_EXPORT", "/tmp/m.prom")
    assert _config.metrics_export_path() == "/tmp/m.prom"
    monkeypatch.setenv("HOROVOD_METRICS_INTERVAL_MS", "10")
    assert _config.metrics_interval_ms() == 100  # clamped floor
    monkeypatch.setenv("HOROVOD_STRAGGLER_MS", "250")
    assert _config.straggler_ms() == 250
    monkeypatch.setenv("HOROVOD_STRAGGLER_PATIENCE", "0")
    assert _config.straggler_patience() == 1  # clamped floor
