"""Unified metrics plane — the Python half (docs/metrics.md).

Merges the native registry's JSON snapshot (``csrc/hvd/metrics.cc``,
read through the single ``hvd_metrics_snapshot`` getter) with the
Python-plane counters that never touch the native core: Retrier
retries, fault injections, shm/stripe fallback armings, elastic
evictions and drains. Surfaced as ``hvd.metrics()`` /
``hvd.metrics_report()`` and, behind ``HOROVOD_METRICS_EXPORT``
(default off = byte-identical behavior), published periodically as a
Prometheus textfile plus Chrome-tracing counter ("C" phase) events in
the active timeline.

The straggler warnings the native detector drains through the snapshot
become ``STRAGGLER_WARNING`` timeline instants here — the Python plane
owns the timeline, the native plane owns the per-rank ready
timestamps.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import threading
import time
from typing import Optional

from . import config as _config
from . import logging as _log

# ---- Python-plane counters -------------------------------------------------
#
# One flat namespace of monotonically increasing ints. Callers use
# dotted names mirroring the subsystem that owns them:
#   retrier.retries        every Retrier backoff taken (faults.py)
#   faults.injected        every fault point that fired (faults.py)
#   chaos.injected         the subset of faults.injected drawn by the
#                          seeded chaos scheduler, HOROVOD_CHAOS_SPEC
#                          (faults.py; docs/self-healing.md)
#   shm.attach_fallback    ring.shm.attach seam armed a forced TCP
#                          fallback for this world (host_world.py)
#   stripe.connect_fallback  the stripe sibling (host_world.py)
#   elastic.evictions      driver-side liveness evictions (driver.py)
#   elastic.drains         commit-marked graceful drains (driver.py)
#   model.latent_layers, model.mtp_modules  what a decoder step was built
#                          with, where it has either (models/transformer.py)
#   kernels.traced.<kernel>, kernels.grouped.flash_<kind>,
#   kernels.blockcausal.flash_<kind>  the pallas_calls the host traced,
#                          those with a grouped K side and those under the
#                          block-causal rule (ops/pallas_attention.py's
#                          _log_plan; ops/ssd.py, parallel/moe.py)
#   kernels.kda_<kind>.heads_per_step  the heads a grid step of each traced
#                           kda_fwd / kda_bwd call carries (ops/kda.py)
#   kernels.eva.merged_operands  differentiated passes of EVA attention
#                          traced whose two calls read one merged q: 2
#                          after a train step's (ops/eva_attention.py)
#   head.blocks_with_gradients_traced  differentiated traces of the head
#                          and loss by blocks, whose forward pass makes the
#                          gradients: 1 after a train step's, 0 after an
#                          evaluation's (models/transformer.py::block_nll)
#
# The native snapshot carries the self-healing counters alongside these
# (link.reconnects / link.resume_chunks_discarded /
# link.stale_epoch_rejected / epoch — csrc/hvd/operations.cc).

_lock = threading.Lock()
_counters: dict = {}


def inc(name: str, n: int = 1) -> None:
    """Bump a Python-plane counter (thread-safe, near-zero cost)."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict:
    """A copy of the Python-plane counters."""
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Zero the Python-plane counters and forget the spans (tests)."""
    global _spans_refused
    with _lock:
        _counters.clear()
        del _spans[:]
        _spans_refused = 0
    _local.__dict__.clear()


# ---- spans -----------------------------------------------------------------
#
# What a job crosses before its first step, as intervals on the
# profiler's clock (docs/diagnostics.md, "Set-up spans"). A span is
#   {"id", "parent", "name", "start_ns", "end_ns", "thread", "counts"}
# with ``parent`` the innermost span open on the same thread (None: a
# root), both times ``time.time_ns()`` (the clock of jax's own phase
# spans and of the host lines of an ``.xplane.pb``) and ``counts`` a few
# integers known at the boundary. Kept in one list under the module's
# lock and written nowhere unless asked: ``spans()``, ``hvd.metrics()``
# under "spans", ``hvd.metrics_report()`` as a table. jax's compile
# phases (trace, lowering to MLIR, compile or cache read) are spans too,
# named ``<phase>:<module>``, opened and closed by ``jax.monitoring``
# listeners that are registered at the first span that opens or closes
# with jax already imported.

# Set-up is tens of spans of the program's and three a program jax
# builds; a loop that opens them by mistake is refused past this many
# and counted, so it cannot grow a training job's memory.
SPAN_CAP = 4096
_PHASE_EVENT = re.compile(r"^/jax/core/compile/(\w+)_duration$")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

_spans: list = []
_spans_refused = 0
_listening = False
_local = threading.local()


def _mine():
    """This thread's own: ``open``, its open spans, outermost first;
    ``phases``, jax's phases in progress (None for one not recorded);
    ``cache``, what the compile cache has said since the last compile."""
    if not hasattr(_local, "open"):
        _local.open, _local.phases, _local.cache = [], [], {}
    return _local


def _open(name, start_ns, counts):
    """A record under the innermost open span of this thread, or None
    past the cap."""
    global _spans_refused
    stack = _mine().open
    with _lock:
        if len(_spans) >= SPAN_CAP:
            _spans_refused += 1
            return None
        rec = {"id": len(_spans),
               "parent": stack[-1]["id"] if stack else None, "name": name,
               "start_ns": start_ns, "end_ns": None,
               "thread": threading.get_ident(), "counts": counts}
        _spans.append(rec)
    stack.append(rec)
    return rec


def _close(rec, end_ns):
    if rec is not None:
        rec["end_ns"] = end_ns
        _mine().open.remove(rec)


def _module_name(fun_name: str) -> str:
    """The name jax gives the module of ``fun_name``: the trace phase
    reports the function's (``hvd_decoder_step``), lowering and compile
    the wrapped one (``jit(hvd_decoder_step)``)."""
    if "(" not in fun_name:
        fun_name = f"jit({fun_name})"
    return re.sub(r"[^\w.-]", "_", fun_name).rstrip("_")


def _on_phase_start(event, start, fun_name="", **_):
    """jax reports where a phase starts as a scalar, the time. A trace
    inside another phase is not recorded and stays in that phase's time:
    every ``jnp`` function a trace calls is a ``jit`` of its own, seven
    thousand of them in a ResNet-50 job."""
    phase = _PHASE_EVENT.match(event)
    if phase is None:
        return
    mine = _mine()
    rec = None
    if not (mine.phases and phase.group(1) == "jaxpr_trace"):
        rec = _open(f"{phase.group(1)}:{_module_name(fun_name)}",
                    int(start * 1e9), {})
    mine.phases.append(rec)


def _on_phase_end(event, start, end, **_):
    mine = _mine()
    if mine.phases and _PHASE_EVENT.match(event):
        rec = mine.phases.pop()
        if rec is not None and rec["name"].startswith("backend_compile:"):
            rec["counts"].update(mine.cache)
            mine.cache = {}
        _close(rec, int(end * 1e9))


def _on_event(event, **_):
    if event in (_CACHE_HIT, _CACHE_MISS):
        _mine().cache["cache_hit"] = int(event == _CACHE_HIT)


def _on_duration(event, duration, **_):
    if event == _CACHE_READ:
        _mine().cache["retrieval_ms"] = round(1e3 * duration)


def _jax():
    """jax if the process has imported it, and never imported from here:
    this module is read before jax is."""
    global _listening
    jax = sys.modules.get("jax")
    monitoring = getattr(jax, "monitoring", None)
    if monitoring is not None and not _listening:
        with _lock:
            if not _listening:
                _listening = True
                monitoring.register_scalar_listener(_on_phase_start)
                monitoring.register_event_time_span_listener(_on_phase_end)
                monitoring.register_event_listener(_on_event)
                monitoring.register_event_duration_secs_listener(
                    _on_duration)
    return jax


class span:
    """``with metrics.span("state.init", leaves=n) as s: ...; s.add(bytes=b)``
    records the block as a span and shows it, under the same name, on
    the host lines of a profile taken with ``jax.profiler.start_trace``
    (a TraceMe costs nothing measurable while no profiler runs). As a
    decorator it records every call of the function."""

    def __init__(self, name: str, **counts: int):
        self.name = name
        self.counts = counts
        self._rec = self._annotation = None

    def add(self, **counts: int) -> None:
        """Add to the span's counts, before it closes."""
        for key, n in counts.items():
            self.counts[key] = self.counts.get(key, 0) + int(n)

    def __enter__(self):
        profiler = getattr(_jax(), "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._rec = _open(self.name, time.time_ns(), self.counts)
        return self

    def __exit__(self, *exc):
        _close(self._rec, time.time_ns())
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _jax()
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with span(self.name, **self.counts):
                return fn(*args, **kwargs)
        return spanned


def note(**counts: int) -> None:
    """Add to the counts of this thread's innermost open span; nothing
    where none is open."""
    stack = _mine().open
    if stack:
        held = stack[-1]["counts"]
        for key, n in counts.items():
            held[key] = held.get(key, 0) + int(n)


def tree_counts(tree) -> dict:
    """``leaves`` and ``bytes`` of a pytree of arrays (or of tracers)."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "dtype")]
    return {"leaves": len(leaves),
            "bytes": sum(x.size * x.dtype.itemsize for x in leaves)}


def _covered_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total, reach = total + end - start, end
        elif end > reach:
            total, reach = total + end - reach, end
    return total


def spans() -> list:
    """A copy of the spans recorded so far, in the order they were
    admitted, each with ``self_ns``: its duration less the part its
    children cover. A span still open ends now."""
    now = time.time_ns()
    with _lock:
        out = [dict(r, counts=dict(r["counts"]),
                    end_ns=now if r["end_ns"] is None else r["end_ns"])
               for r in _spans]
    inside = {}
    for r in out:
        if r["parent"] is not None:
            outer = out[r["parent"]]
            inside.setdefault(r["parent"], []).append(
                (max(r["start_ns"], outer["start_ns"]),
                 min(r["end_ns"], outer["end_ns"])))
    for r in out:
        r["self_ns"] = max(0, r["end_ns"] - r["start_ns"]
                           - _covered_ns(inside.get(r["id"], ())))
    return out


def spans_refused() -> int:
    """Spans that found the list at ``SPAN_CAP`` and were not recorded."""
    with _lock:
        return _spans_refused


# ---- native snapshot access ------------------------------------------------


def live_native_core():
    """The process's live NativeCore: the XLA engine's when one runs,
    else the host (process-rank) world's. None in pure-direct mode or
    before init — the ONE core-resolution rule every observability
    surface shares (``hvd.stall_report``/``ring_traffic``/``metrics``)."""
    from . import state as _state

    st = _state.global_state()
    if st.initialized and st.engine is not None:
        core = getattr(st.engine, "native_core", None)
        if core is not None:
            return core
    from . import host_world as _host_world

    world = _host_world.world()
    return world._core if world.initialized else None


def _active_timeline():
    from . import state as _state

    st = _state.global_state()
    return st.timeline if st.initialized else None


def _emit_straggler_instants(native: Optional[dict]) -> None:
    """Drained straggler events -> STRAGGLER_WARNING timeline instants
    (when a timeline is active; the events also live in the returned
    snapshot either way)."""
    if not native:
        return
    events = native.get("straggler", {}).get("events", ())
    if not events:
        return
    timeline = _active_timeline()
    if timeline is None:
        return
    from . import timeline as _timeline

    for ev in events:
        timeline.instant(_timeline.STRAGGLER_WARNING,
                         {"rank": ev.get("rank"),
                          "lag_ms": ev.get("lag_ms")})


def snapshot(drain: bool = True) -> dict:
    """The merged metrics view behind ``hvd.metrics()``:

    ``{"python": {counter: value}, "native": {...} | None,
    "spans": [...]}``

    ``spans`` is :func:`spans`, the set-up spans recorded so far;
    ``native`` is the parsed unified snapshot (counters, log2
    histograms, straggler state) or None when no native core is live.
    With ``drain`` (the default), pending straggler warning events are
    consumed into ``native["straggler"]["events"]`` and mirrored as
    ``STRAGGLER_WARNING`` timeline instants; monitors that must not
    steal events pass ``drain=False``."""
    native = None
    core = live_native_core()
    if core is not None:
        flags = core.METRICS_DRAIN_STRAGGLER if drain else 0
        native = core.metrics_snapshot(flags) or None
        if drain:
            _emit_straggler_instants(native)
    return {"python": counters(), "native": native, "spans": spans()}


# ---- histogram math --------------------------------------------------------


def percentiles(hist: dict, qs=(50, 90, 99)) -> dict:
    """Approximate percentiles of a native log2 histogram (value taken
    at each covering bucket's upper bound, 2^(i+1); exact enough for
    "did p99 gather wait regress 10x", which is what log2 buckets are
    for). ``hist`` is the snapshot shape ``{"count":..., "buckets":
    [[index, count], ...]}``. Returns {"p50": v, ...} (zeros when
    empty)."""
    total = int(hist.get("count", 0))
    out = {f"p{q}": 0 for q in qs}
    if total <= 0:
        return out
    buckets = sorted((int(b), int(c)) for b, c in hist.get("buckets", ()))
    for q in qs:
        target = total * q / 100.0
        seen = 0
        val = 0
        for b, c in buckets:
            seen += c
            if seen >= target:
                val = 2 ** (b + 1)
                break
        out[f"p{q}"] = val
    return out


def report_text(snap: Optional[dict] = None) -> str:
    """Human-readable rendering of a merged snapshot (the string behind
    ``hvd.metrics_report()``): counters, the set-up spans by name
    (count, total and self time), then each non-empty histogram
    with count / approximate p50/p99 / max, then straggler state.
    Reads with ``drain=False`` — a human glance must not steal pending
    straggler events from ``hvd.metrics()``, which renders them."""
    snap = snap if snap is not None else snapshot(drain=False)
    lines = ["== horovod_tpu metrics =="]
    py = snap.get("python") or {}
    native = snap.get("native")
    if py:
        lines.append("-- python counters --")
        for k in sorted(py):
            lines.append(f"{k}: {py[k]}")
    lines += _span_table(snap.get("spans") or ())
    if not native:
        lines.append("native core: absent (pure-XLA direct mode or "
                     "not initialized)")
        return "\n".join(lines) + "\n"
    lines.append("-- native counters --")
    for k in sorted(native.get("counters", {})):
        lines.append(f"{k}: {native['counters'][k]}")
    lines.append("-- histograms (us) --")
    for name in sorted(native.get("histograms", {})):
        h = native["histograms"][name]
        if not h.get("count"):
            continue
        p = percentiles(h, (50, 99))
        lines.append(f"{name}: n={h['count']} p50~{p['p50']} "
                     f"p99~{p['p99']} max={h['max']}")
    st = native.get("straggler", {})
    lines.append(f"straggler: warnings={st.get('warnings', 0)} "
                 f"last_rank={st.get('last_rank', -1)} "
                 f"last_lag_ms={st.get('last_lag_ms', 0)}")
    return "\n".join(lines) + "\n"


def _span_table(records) -> list:
    """The report's lines for the spans: one a name, in the order each
    name first opened."""
    rows = {}
    for r in records:
        row = rows.setdefault(r["name"], [0, 0, 0])
        row[0] += 1
        row[1] += r["end_ns"] - r["start_ns"]
        row[2] += r["self_ns"]
    if not rows:
        return []
    lines = ["-- spans (ms) --"]
    lines += [f"{name}: n={n} total={total / 1e6:.1f} self={own / 1e6:.1f}"
              for name, (n, total, own) in rows.items()]
    refused = spans_refused()
    if refused:
        lines.append(f"spans refused past {SPAN_CAP}: {refused}")
    return lines


# ---- Prometheus textfile exporter ------------------------------------------


def _prom_name(name: str) -> str:
    return "hvd_" + name.replace(".", "_").replace("-", "_")


def prometheus_text(snap: Optional[dict] = None) -> str:
    """Render a merged snapshot in node-exporter textfile format:
    counters as gauges, log2 histograms as Prometheus histograms with
    ``le`` = the bucket upper bounds (2^(i+1) microseconds)."""
    snap = snap if snap is not None else snapshot(drain=False)
    out = []
    py = snap.get("python") or {}
    for k in sorted(py):
        n = _prom_name(k)
        out.append(f"# TYPE {n} counter")
        out.append(f"{n} {py[k]}")
    native = snap.get("native")
    if native:
        for k in sorted(native.get("counters", {})):
            v = native["counters"][k]
            n = _prom_name(k)
            out.append(f"# TYPE {n} gauge")
            out.append(f"{n} {v}")
        for name in sorted(native.get("histograms", {})):
            h = native["histograms"][name]
            n = _prom_name(name)
            out.append(f"# TYPE {n} histogram")
            cum = 0
            for b, c in sorted((int(b), int(c))
                               for b, c in h.get("buckets", ())):
                cum += c
                out.append(f'{n}_bucket{{le="{2 ** (b + 1)}"}} {cum}')
            # The snapshot reads count before the bucket array while
            # recorders increment bucket-then-count (relaxed): a Record
            # landing between the reads makes sum(buckets) == count+1.
            # +Inf/_count must stay >= every explicit bucket or the
            # series is an invalid decreasing histogram.
            total = max(cum, int(h.get("count", 0)))
            out.append(f'{n}_bucket{{le="+Inf"}} {total}')
            out.append(f"{n}_sum {h.get('sum', 0)}")
            out.append(f"{n}_count {total}")
        st = native.get("straggler", {})
        out.append("# TYPE hvd_straggler_warnings counter")
        out.append(f"hvd_straggler_warnings {st.get('warnings', 0)}")
        out.append("# TYPE hvd_straggler_last_rank gauge")
        out.append(f"hvd_straggler_last_rank {st.get('last_rank', -1)}")
    return "\n".join(out) + "\n"


class MetricsPump(threading.Thread):
    """The exporter thread (rank-side, armed ONLY by
    ``HOROVOD_METRICS_EXPORT``): every interval, snapshot once and
    publish twice — atomically rewrite the textfile, and (when a
    timeline is active) emit Chrome-tracing counter events plus any
    drained STRAGGLER_WARNING instants. Daemonized and stop()-able; a
    publish failure logs and keeps the thread alive (observability must
    never take the job down)."""

    def __init__(self, path: str, interval_ms: int):
        super().__init__(name="hvd-metrics-pump", daemon=True)
        self._path = path
        self._interval_s = max(0.1, interval_ms / 1000.0)
        # NOT self._stop: threading.Thread owns a private _stop() method
        # (CPython's tstate cleanup calls it) — shadowing it with an
        # Event breaks Thread.join on 3.10.
        self._stop_evt = threading.Event()
        # Last observed native link.reconnects value: a growth between
        # publishes becomes a LINK_RECONNECT timeline instant (the pump
        # is the only reader, so plain int is fine).
        self._last_reconnects = 0

    def stop(self):
        self._stop_evt.set()
        self.join(timeout=5.0)

    def publish_once(self):
        # Drain straggler events only when a timeline exists to receive
        # them as instants — otherwise the pump would silently discard
        # events that hvd.metrics() promises to deliver (the textfile
        # renders cumulative straggler state either way).
        snap = snapshot(drain=_active_timeline() is not None)
        text = prometheus_text(snap)
        tmp = f"{self._path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, self._path)
        timeline = _active_timeline()
        native = snap.get("native")
        if timeline is not None and native:
            c = native.get("counters", {})
            timeline.counter("hvd_bytes", {
                "bytes_sent": c.get("bytes_sent", 0),
                "cross_bytes": c.get("cross_bytes", 0),
                "shm_bytes": c.get("shm_bytes", 0),
            })
            timeline.counter("hvd_control", {
                "cache_hits": c.get("cache_hits", 0),
                "cycles": c.get("cycles", 0),
                "pending": c.get("pending", 0),
            })
            reconnects = int(c.get("link.reconnects", 0))
            if reconnects > self._last_reconnects:
                from . import timeline as _timeline

                timeline.instant(_timeline.LINK_RECONNECT,
                                 {"reconnects": reconnects})
            self._last_reconnects = reconnects

    def run(self):
        while not self._stop_evt.wait(self._interval_s):
            try:
                self.publish_once()
            # The exporter is best-effort by contract: a transient
            # write/snapshot error must not kill the pump (or the
            # training job).
            except Exception as e:
                _log.warning(f"metrics export failed: {e}")
        # Final publish so short jobs still leave a file behind.
        try:
            self.publish_once()
        # Same best-effort contract on the shutdown flush.
        except Exception as e:
            _log.debug(f"final metrics export failed: {e}")


_pump: Optional[MetricsPump] = None


def maybe_start_pump() -> Optional[MetricsPump]:
    """Start the exporter iff ``HOROVOD_METRICS_EXPORT`` is set (called
    from ``hvd.init``). Unset = nothing starts, nothing is written —
    the byte-identical default (regression-tested)."""
    global _pump
    path = _config.metrics_export_path()
    if not path or _pump is not None:
        return _pump
    _pump = MetricsPump(path, _config.metrics_interval_ms())
    _pump.start()
    return _pump


def stop_pump() -> None:
    """Stop the exporter (called from ``hvd.shutdown``); flushes one
    final snapshot to the textfile."""
    global _pump
    if _pump is not None:
        _pump.stop()
        _pump = None
