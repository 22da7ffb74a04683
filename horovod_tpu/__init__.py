"""horovod_tpu — a TPU-native distributed training framework.

Capability parity with Horovod (reference: tgravescs/horovod v0.19.2),
re-architected for TPU: XLA collectives over ICI/DCN replace NCCL/MPI/Gloo,
``jax.sharding.Mesh`` topology replaces MPI rank discovery, and the
coordination control plane lives in a native runtime library.

Typical use (JAX-native, mirrors ``import horovod.torch as hvd`` scripts)::

    import horovod_tpu as hvd

    hvd.init()
    # eager API
    summed = hvd.allreduce(per_chip_grads, op=hvd.Sum)
    # in-jit API (inside shard_map/pjit over hvd.mesh())
    grads = hvd.xla.allreduce(grads, op=hvd.Average)

Framework bindings live in ``horovod_tpu.torch``, ``horovod_tpu.tensorflow``,
``horovod_tpu.keras`` (import the one matching your framework, as with the
reference).
"""

from typing import List, Optional

from .common import metrics as _metrics

# Set-up span (docs/diagnostics.md): this file, first statement to last.
_import_span = _metrics.span("import:horovod_tpu").__enter__()

from .version import __version__  # noqa: E402,F401
from .common import exceptions  # noqa: F401
from .common.exceptions import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from .common.state import (  # noqa: F401
    ccl_built,
    cross_rank,
    cross_size,
    ddl_built,
    gloo_built,
    gloo_enabled,
    hierarchical_mesh,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rank,
    shutdown,
    size,
    tpu_available,
    xla_built,
)
from .common.state import global_state as _global_state
from .common.compression import Compression  # noqa: F401
from .ops import xla  # noqa: F401
from .ops.xla import Adasum, Average, Max, Min, ReduceOp, Sum  # noqa: F401


def _engine():
    st = _global_state()
    if not st.initialized or st.engine is None:
        from .common.exceptions import NotInitializedError

        raise NotInitializedError("collective API")
    return st.engine


# ---- eager async API (parity: hvd.allreduce_async_/poll/synchronize) -------


def allreduce_async(tensor, name: Optional[str] = None, op: int = Average,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0) -> int:
    """Default op is Average, same as the sync form — the reference's
    async flavors average by default too (``torch/mpi_ops.py:91-129``)."""
    return _engine().allreduce_async(
        tensor, name=name, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor)


def allreduce(tensor, name: Optional[str] = None, op: int = Average,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Eager allreduce. Default op is Average, matching the reference's
    Python-level default (``torch/mpi_ops.py:91-129``)."""
    return synchronize(allreduce_async(
        tensor, name=name, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor))


def grouped_allreduce_async(tensors: List, name: Optional[str] = None,
                            op: int = Average, prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0) -> int:
    return _engine().grouped_allreduce_async(
        tensors, name=name, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor)


def grouped_allreduce(tensors: List, name: Optional[str] = None,
                      op: int = Average, prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    return synchronize(grouped_allreduce_async(
        tensors, name=name, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor))


def allgather_async(tensor, name: Optional[str] = None) -> int:
    return _engine().allgather_async(tensor, name=name)


def allgather(tensor, name: Optional[str] = None):
    return synchronize(allgather_async(tensor, name=name))


def broadcast_async(tensor, root_rank: int, name: Optional[str] = None) -> int:
    return _engine().broadcast_async(tensor, root_rank, name=name)


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    return synchronize(broadcast_async(tensor, root_rank, name=name))


def reducescatter_async(tensor, name: Optional[str] = None, op: int = Sum) -> int:
    return _engine().reducescatter_async(tensor, name=name, op=op)


def reducescatter(tensor, name: Optional[str] = None, op: int = Sum):
    return synchronize(reducescatter_async(tensor, name=name, op=op))


def alltoall_async(tensor, name: Optional[str] = None) -> int:
    return _engine().alltoall_async(tensor, name=name)


def alltoall(tensor, name: Optional[str] = None):
    return synchronize(alltoall_async(tensor, name=name))


def poll(handle: int) -> bool:
    """True if the collective behind ``handle`` has completed."""
    return _engine().poll(handle)


def synchronize(handle: int):
    """Block until the collective completes and return its result."""
    return _engine().synchronize(handle)


def barrier():
    """Synchronize all participants (capability extension; the reference
    gained hvd.barrier() post-0.19)."""
    _engine().barrier()


def stall_report() -> str:
    """Drain and return the native stall inspector's accumulated warnings
    (reference ``stall_inspector.cc``: the coordinator reports tensors
    some ranks submitted and others never did — the classic desync
    signature). ALWAYS returns ``str``: the empty string — never None,
    never an exception — when nothing stalled, when ``hvd.init()``
    hasn't run, or when the native core is absent (pure-XLA direct
    mode); the shape is pinned by tests/test_metrics.py.

    Consuming a non-empty report also records a ``STALL_WARNING`` instant
    in the timeline (when one is active), so stalls line up with the
    collectives that caused them in post-mortems."""
    core = _native_core()
    if core is None:
        return ""
    st = _global_state()
    report = core.stall_report()
    if report and st.initialized and st.timeline is not None:
        from .common import timeline as _timeline_mod

        st.timeline.instant(_timeline_mod.STALL_WARNING,
                            {"report": report})
    return report


def liveness_report() -> str:
    """Drain and return the native liveness plane's accumulated events
    (docs/liveness.md): ``SUSPECT``/``EVICT``/``DRAIN``/``RECOVER``
    lines from the controller's heartbeat state machine, one per
    transition. ALWAYS returns ``str``: the empty string — never None,
    never an exception — when the plane is disabled
    (``HOROVOD_HEARTBEAT_MS=0``, the default), when nothing happened,
    when ``hvd.init()`` hasn't run, or when the native core is absent
    (pure-XLA direct mode); the shape is pinned by
    tests/test_metrics.py. Like ``stall_report()``, reading consumes —
    the drain rides the unified metrics snapshot (docs/metrics.md)."""
    core = _native_core()
    if core is None:
        return ""
    return core.liveness_report()


def _native_core():
    """The process's live NativeCore: the XLA engine's when one runs,
    else the host (process-rank) world's. None in pure-direct mode.
    (One rule, owned by common/metrics.py — every observability surface
    resolves the core identically.)"""
    return _metrics.live_native_core()


def metrics() -> dict:
    """The unified metrics snapshot (docs/metrics.md):
    ``{"python": {...}, "native": {...} | None, "spans": [...]}``.

    ``python`` holds the Python-plane counters (Retrier retries, fault
    injections, shm/stripe fallback armings, elastic evictions/drains,
    the kernels the host traced); ``spans`` what the job crossed before
    its first step, on the profiler's clock (docs/diagnostics.md,
    "Set-up spans");
    ``native`` is the registry snapshot from the single
    ``hvd_metrics_snapshot`` getter — traffic/control counters, the
    log2 latency histograms (enqueue→negotiated→executed per op class,
    background-cycle duration, coordinator per-rank gather wait,
    cross/shm/stripe leg timings, per-step rank skew), and the
    straggler detector's state — or None before init / in pure-XLA
    direct mode. Reading drains pending STRAGGLER_WARNING events into
    ``native["straggler"]["events"]`` and mirrors them as timeline
    instants when a timeline is active; counters and histograms are
    cumulative for the world and unaffected by reads."""
    return _metrics.snapshot()


def metrics_report() -> str:
    """Human-readable rendering of :func:`metrics` — counters, each
    non-empty histogram with approximate p50/p99 (log2 buckets), and
    the straggler state. Empty-safe: always returns a string, with or
    without a native core."""
    return _metrics.report_text()


def ring_traffic() -> dict:
    """Host data-plane traffic accounting with the local/cross/shm split.

    Returns a dict with ``bytes_sent`` (every payload byte this process
    moved on the host data plane, TCP and shm), ``local_bytes`` (TCP to
    same-host peers — the loopback legs of the hierarchical collectives
    when the shm transport is off or fell back), ``cross_bytes`` (to
    peers on other hosts: the scarce budget the two-level paths
    minimize; see ``docs/hierarchical.md``), ``shm_bytes`` (payload
    moved through the shared-memory transport's rings with zero socket
    syscalls — with shm active the local leg lives here and
    ``local_bytes`` collapses to ~0; ``docs/shm-transport.md``),
    ``shm`` (True when this rank's shm transport is live — the
    transport choice), ``stripe_bytes`` (payload that rode the striped
    cross-host transport — a subset of ``cross_bytes``, which stays
    byte-identical to the single-socket path; see
    ``docs/cross-transport.md``), ``stripes`` (the stripe count in
    active use: K once a leader pair carries striped traffic, 0 with
    striping off or fully fallen back), the effective
    ``hierarchical_allreduce``/``hierarchical_allgather`` host-plane
    dispatch (autotuner-synced value when present, else the env
    config), and ``tuned`` (True once an autotuner decision reached
    this rank). All zeros/False before init or in pure-XLA direct
    mode."""
    core = _native_core()
    empty = {"bytes_sent": 0, "local_bytes": 0, "cross_bytes": 0,
             "shm_bytes": 0, "shm": False,
             "stripe_bytes": 0, "stripes": 0,
             "hierarchical_allreduce": False,
             "hierarchical_allgather": False, "tuned": False}
    if core is None:
        return empty
    # One native call through the unified snapshot (docs/metrics.md)
    # instead of nine per-counter getters — the consistency invariant
    # (bytes_sent == local + cross + shm) is asserted against this same
    # document in tests/test_metrics.py.
    snap = core.metrics_snapshot()
    if not snap:
        return empty
    c = snap.get("counters", {})
    flags = int(c.get("host_hier_flags", 0))
    return {
        "bytes_sent": int(c.get("bytes_sent", 0)),
        "local_bytes": int(c.get("local_bytes", 0)),
        "cross_bytes": int(c.get("cross_bytes", 0)),
        "shm_bytes": int(c.get("shm_bytes", 0)),
        "shm": bool(c.get("shm_active", 0)),
        "stripe_bytes": int(c.get("stripe_bytes", 0)),
        "stripes": int(c.get("stripes", 0)),
        "hierarchical_allreduce": bool(flags & 1),
        "hierarchical_allgather": bool(flags & 2),
        "tuned": int(c.get("tuned_hier_flags", -1)) >= 0,
    }


def join() -> int:
    """Graceful departure (parity: ``hvd.join()``, ``operations.cc:937-961``).

    A process that calls ``join()`` stops submitting tensors and contributes
    zeros to the remaining processes' allreduces until every process has
    joined (allgather/broadcast while a rank is joined raise an error, as in
    the reference). Returns the last joined participant's global rank. In
    single-controller SPMD mode every chip is driven by one live process, so
    join degenerates to a barrier.
    """
    st = _global_state()
    st.last_joined = _engine().join()
    return st.last_joined


# ---- high-level JAX-native helpers -----------------------------------------


def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a pytree of parameters from ``root_rank`` to all
    participants (parity: ``torch/functions.py:30-226``). In SPMD
    single-controller mode the tree is already consistent process-wide; the
    broadcast runs across processes when there are several."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = [broadcast(l, root_rank, name=f"bcast.param.{i}")
           for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None):
    """Broadcast an arbitrary picklable object (parity:
    ``torch/functions.py`` broadcast_object)."""
    import pickle

    import numpy as np

    st = _global_state()
    if st.process_count == 1:
        return obj  # single controller: nothing to do
    payload = pickle.dumps(obj) if st.process_index == root_rank else b""
    n = int(np.asarray(
        synchronize(allreduce_async(
            np.asarray(len(payload), dtype=np.int64), op=Sum,
            name=(name or "bcast.obj") + ".len"))).max())
    buf = np.zeros(n, dtype=np.uint8)
    if st.process_index == root_rank:
        buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    buf = broadcast(buf, root_rank, name=(name or "bcast.obj") + ".data")
    return pickle.loads(bytes(np.asarray(buf)))


from . import elastic  # noqa: E402,F401


class DistributedOptimizer:
    """Optax gradient-transformation wrapper that averages gradients across
    the mesh (parity: ``hvd.DistributedOptimizer``; see
    ``horovod_tpu.opt`` for the full implementation)."""

    def __new__(cls, optimizer, **kwargs):
        from .opt import DistributedOptimizer as _impl

        return _impl(optimizer, **kwargs)


_import_span.__exit__(None, None, None)
del _import_span
