"""Process-global runtime state and the basics API.

Capability parity with the reference's ``horovod/common/basics.py:22-211``
(init/shutdown/rank/size/local/cross queries) and ``global_state.h:42-122``,
re-designed TPU-first:

- The world is a ``jax.sharding.Mesh`` over all addressable TPU chips, not a
  set of MPI ranks. Every *chip* is a participant; ``size()`` is the number
  of chips in the mesh.
- The reference's GLOBAL/LOCAL/CROSS communicator hierarchy
  (``common.h:111-115``, ``mpi_context.h:78-84``) maps onto TPU topology:
  LOCAL = the chips driven by this process (ICI-connected), CROSS = the
  process/slice grid reached over DCN. ``local_size()``/``cross_size()``
  follow that mapping.
- Multi-host initialization goes through ``jax.distributed`` (gRPC
  coordination service) instead of MPI_Init; the launcher provides the
  coordinator address via ``HOROVOD_CONTROLLER_ADDR/PORT`` env, playing the
  role of the reference's Gloo rendezvous (``gloo_context.cc:40-54``).
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from . import config as _config
from . import logging as _log
from . import metrics as _metrics
from .exceptions import NotInitializedError

# Mesh axis names. "hvd" is the flat data-parallel axis used by the
# Horovod-parity API; hierarchical ops split it into cross ("dcn") x
# local ("ici").
AXIS_GLOBAL = "hvd"
AXIS_CROSS = "dcn"
AXIS_LOCAL = "ici"


class _GlobalState:
    """Singleton mirroring the reference's ``HorovodGlobalState``."""

    def __init__(self):
        self.lock = threading.Lock()
        self.initialized = False
        self.config: Optional[_config.RuntimeConfig] = None
        self.mesh = None  # flat 1-D Mesh over all participating devices
        self.hier_mesh = None  # 2-D Mesh (cross, local) over the same devices
        self.devices: Sequence = ()
        self.local_devices: Sequence = ()
        self.size = 0
        self.local_size = 0
        self.cross_size = 0
        self.rank = 0
        self.local_rank = 0
        self.cross_rank = 0
        self.process_count = 1
        self.process_index = 0
        self.is_homogeneous = True
        self.engine = None  # ops.eager.EagerEngine, attached at init
        self.timeline = None
        self.autotuner = None
        self.elastic_enabled = False
        self.last_joined = -1

    def reset(self):
        self.__init__()


_state = _GlobalState()


def global_state() -> _GlobalState:
    return _state


def _maybe_init_distributed() -> None:
    """Join the multi-process world if the launcher set one up.

    The launcher exports HOROVOD_SIZE (process count), HOROVOD_RANK
    (process index) and HOROVOD_CONTROLLER_ADDR/PORT (the gRPC coordination
    service endpoint) — the TPU-native analog of the reference's env-driven
    Gloo rendezvous (``gloo_context.cc:40-54``).
    """
    import jax

    nproc = _config.size()
    # NOTE: no jax.process_count()/jax.devices() here — any backend query
    # initializes XLA, after which jax.distributed.initialize refuses to
    # run. Use the distributed client's own state to detect re-init.
    from .compat import distributed_is_initialized

    if nproc <= 1 or distributed_is_initialized():
        return
    rank = _config.rank()
    addr = _config.controller_addr()
    port = _config.controller_base_port()
    _log.debug(f"joining distributed world: {rank}/{nproc} via {addr}:{port}")
    jax.distributed.initialize(
        coordinator_address=f"{addr}:{port}",
        num_processes=nproc,
        process_id=rank,
    )


def init(comm=None, devices=None):
    """Initialize the runtime.

    ``comm`` accepts a list of process indices to restrict the world to a
    subset of launched processes (parity with ``hvd.init(comm=[ranks])``,
    reference ``basics.py:33-65``); on TPU the subset must be
    slice-aligned, so we only support the full world or a device subset via
    ``devices``.
    """
    import jax
    from jax.sharding import Mesh

    with _state.lock, _metrics.span("init") as setup:
        if _state.initialized:
            return

        _maybe_init_distributed()

        _state.config = _config.RuntimeConfig.from_env()

        if devices is None:
            all_devices = list(jax.devices())
        else:
            all_devices = list(devices)
        if comm is not None:
            # Restrict to the devices owned by the given process subset.
            keep = set(comm)
            all_devices = [d for d in all_devices if d.process_index in keep]

        local = [d for d in all_devices if d.process_index == jax.process_index()]

        _state.devices = all_devices
        _state.local_devices = local
        _state.size = len(all_devices)
        _state.local_size = len(local)
        _state.process_count = jax.process_count()
        _state.process_index = jax.process_index()
        _state.cross_size = max(
            1, len({d.process_index for d in all_devices})
        )
        _state.cross_rank = _state.process_index
        # rank = lowest participant id owned by this process; participant ids
        # follow mesh order (process-major, so contiguous per process).
        _state.rank = (
            all_devices.index(local[0]) if local else 0
        )
        _state.local_rank = 0
        sizes = {}
        for d in all_devices:
            sizes[d.process_index] = sizes.get(d.process_index, 0) + 1
        _state.is_homogeneous = len(set(sizes.values())) <= 1
        setup.add(size=_state.size)

        with _metrics.span("mesh", devices=_state.size):
            mesh_devices = np.array(all_devices, dtype=object)
            _state.mesh = Mesh(mesh_devices, (AXIS_GLOBAL,))
            if _state.is_homogeneous and _state.local_size > 0:
                hier = mesh_devices.reshape(_state.cross_size,
                                            _state.local_size)
                _state.hier_mesh = Mesh(hier, (AXIS_CROSS, AXIS_LOCAL))

        from ..ops.eager import EagerEngine

        with _metrics.span("engine.start"):
            _state.engine = EagerEngine(_state)

        if _state.config.timeline_filename:
            from .timeline import Timeline

            _state.timeline = Timeline(
                _state.config.timeline_filename,
                mark_cycles=_state.config.timeline_mark_cycles,
            )
            if _state.engine.native_core is not None:
                # Record per-rank negotiation ticks while the timeline is
                # active (reference NegotiateRankReady).
                _state.engine.native_core.set_record_negotiation(True)

        if _state.config.autotune and _state.engine.native_core is None:
            _log.warning(
                "HOROVOD_AUTOTUNE requested but the native runtime is "
                "unavailable (direct mode has no tunable cycle/fusion "
                "machinery); autotuning disabled")
        elif _state.config.autotune and _state.rank != 0:
            # The tuner runs only on the coordinator (as in the reference);
            # its chosen (cycle_ms, fusion_bytes) ride every response
            # broadcast and are applied by the native worker cycle
            # (Controller::SynchronizeParameters parity, controller.cc:33-47;
            # see csrc/hvd/controller.cc WorkerCycle).
            _log.debug("autotune: tuner on coordinator; this rank applies "
                       "synced parameters")
        elif _state.config.autotune:
            from .parameter_manager import ParameterManager

            cfg = _state.config

            def _publish_xla_cap(nbytes: int) -> None:
                # Publish the tuned threshold into the live config, where
                # common/fusion.resolve_bucket_cap("auto") reads it — the
                # tuner's (fusion MB, cycle ms) point governs the XLA
                # plane's bucket cap as well as the host plane's cycle
                # fusion (tensor-fusion v2; steps built after this pick
                # the new cap up). SINGLE-CONTROLLER ONLY (gated below):
                # in a multi-process world this config lives on rank 0
                # alone — "auto" steps rebuilt after tuning would bucket
                # on rank 0 but stay monolithic elsewhere, divergent
                # collective sequences in one SPMD program. Workers
                # receive tuned parameters through the native response
                # sync, which does not touch their Python RuntimeConfig.
                cfg.fusion_threshold_bytes = int(nbytes)
                cfg.fusion_threshold_explicit = True

            def _publish_compression(mode: str) -> None:
                # Same live-config publish as the bucket cap, for the
                # compression mode: resolve_compression("auto") reads it,
                # so "auto"-built steps adopt the tuner's pick at their
                # next build. SINGLE-CONTROLLER ONLY (same divergence
                # argument as the cap).
                cfg.compression = mode
                cfg.compression_explicit = True

            # The tuner explores compression ONLY when the user opted in
            # (HOROVOD_COMPRESSION explicitly set to a non-none mode):
            # compression changes numerics, and silently quantizing
            # gradients because it benched faster is not the tuner's
            # call. The grid then answers "does the requested mode
            # actually pay on this model?" — none vs the configured mode.
            # The samples are real A/Bs: the eager engine resolves the
            # live mode per program build (ops/eager.py
            # _exec_grouped_allreduce, mode in the cache key), so each
            # published candidate recompiles the negotiated collectives
            # with that wire format before the sample is scored — and
            # the score's nbytes are *application* bytes, invariant
            # across modes, so bytes/sec genuinely ranks the modes by
            # collective speed.
            comp_candidates = ()
            if cfg.compression_explicit and cfg.compression != "none":
                comp_candidates = ("none", cfg.compression)

            # Stripe grid (docs/cross-transport.md): only when the user
            # opted in (HOROVOD_STRIPES > 1) — the tuner then answers
            # "does the configured striping actually pay on this
            # fabric?" by A/B-ing single-socket vs K stripes through
            # the frame-synced set_stripes apply. The hierarchy gate
            # below (tune_hierarchical) keeps it off worlds with no
            # cross leader leg to stripe.
            stripe_candidates = ()
            if _config.stripes() > 1:
                stripe_candidates = (1, _config.stripes())

            def _publish_zero_prefetch(depth: int) -> None:
                # Same live-config publish as the bucket cap, for the
                # stage-3 gather prefetch depth:
                # fusion.resolve_prefetch_depth("auto") reads it, so
                # "auto"-built stage-3 steps re-resolve and recompile at
                # the new depth on their next call. SINGLE-CONTROLLER
                # ONLY (same divergence argument as the cap). Depth
                # never changes numerics — only the gather dataflow
                # chain — so the tuner may pick freely.
                cfg.zero_prefetch = int(depth)
                cfg.zero_prefetch_explicit = True

            # Prefetch grid (docs/zero.md): only when ZeRO stage 3 is in
            # force — on stage-1/2 worlds there are no forward gathers
            # to pace, and the grid would score noise against noise.
            # Depths 0 (serialized), 1 (default), 2: the marginal win of
            # deeper in-flight windows decays fast while the gathered-
            # buffer watermark grows linearly.
            zero_prefetch_candidates = ()
            if _config.zero_stage() == 3:
                zero_prefetch_candidates = (0, 1, 2)

            if _state.process_count > 1:
                _log.debug(
                    "autotune: XLA bucket-cap/compression/prefetch "
                    "publish disabled in multi-process worlds (set "
                    "HOROVOD_FUSION_THRESHOLD / HOROVOD_COMPRESSION / "
                    "HOROVOD_ZERO_PREFETCH explicitly — same env "
                    "everywhere — to govern the compiled path)")
                _publish_xla_cap = None
                _publish_compression = None
                comp_candidates = ()
                _publish_zero_prefetch = None
                zero_prefetch_candidates = ()

            core = _state.engine.native_core
            _state.autotuner = ParameterManager(
                core, warmup_samples=cfg.autotune_warmup_samples,
                steps_per_sample=cfg.autotune_steps_per_sample,
                max_samples=cfg.autotune_bayes_opt_max_samples,
                gp_noise=cfg.autotune_gaussian_process_noise,
                log_file=cfg.autotune_log,
                initial_cycle_ms=cfg.cycle_time_ms,
                initial_fusion_bytes=cfg.fusion_threshold_bytes,
                # Categorical phase only when the hierarchy actually
                # spans hosts — with cross_size 1 the hier variants can
                # only lose (or win by noise), and the grid would burn
                # 4 sample windows on a meaningless choice.
                tune_hierarchical=(_state.hier_mesh is not None
                                   and _state.cross_size > 1),
                xla_cap_setter=_publish_xla_cap,
                compression_setter=(_publish_compression
                                    if comp_candidates else None),
                compression_candidates=comp_candidates,
                stripe_candidates=stripe_candidates,
                zero_prefetch_setter=(_publish_zero_prefetch
                                      if zero_prefetch_candidates else None),
                zero_prefetch_candidates=zero_prefetch_candidates)

        _state.initialized = True

        # Metrics exporter (docs/metrics.md): ONLY when the operator set
        # HOROVOD_METRICS_EXPORT — unset keeps init byte-identical to
        # pre-metrics builds (no thread, no file, no timeline counter
        # events; regression-tested).
        _metrics.maybe_start_pump()

        _log.info(
            f"horovod_tpu initialized: size={_state.size} "
            f"local_size={_state.local_size} cross_size={_state.cross_size} "
            f"platform={all_devices[0].platform if all_devices else 'none'}"
        )


def shutdown():
    """Tear down the runtime (parity: ``horovod_shutdown``)."""
    with _state.lock:
        if not _state.initialized:
            return
        from . import metrics as _metrics

        # Stop the exporter BEFORE the engine/timeline go away: the
        # final flush still sees a live core and an open timeline.
        _metrics.stop_pump()
        if _state.engine is not None:
            _state.engine.shutdown()
        if _state.timeline is not None:
            _state.timeline.close()
        _state.reset()


def is_initialized() -> bool:
    return _state.initialized


def _require_init(name: str) -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError(name)
    return _state


def size() -> int:
    """Number of participants (TPU chips) in the world."""
    return _require_init("size").size


def local_size() -> int:
    """Number of participants driven by this process (ICI-local group)."""
    return _require_init("local_size").local_size


def cross_size() -> int:
    """Number of processes / DCN endpoints (one per host or slice)."""
    return _require_init("cross_size").cross_size


def rank() -> int:
    """Lowest participant id owned by this process.

    With one process per host driving N chips, ranks are ``process_index*N``;
    rank 0 is always the coordinator process, so ``if hvd.rank() == 0:``
    checkpointing idioms from the reference work unchanged.
    """
    return _require_init("rank").rank


def local_rank() -> int:
    return _require_init("local_rank").local_rank


def cross_rank() -> int:
    return _require_init("cross_rank").cross_rank


def is_homogeneous() -> bool:
    return _require_init("is_homogeneous").is_homogeneous


def mesh():
    """The flat 1-D ``jax.sharding.Mesh`` over all participants."""
    return _require_init("mesh").mesh


def hierarchical_mesh():
    """The (cross, local) 2-D mesh: DCN x ICI, or None if inhomogeneous."""
    return _require_init("hierarchical_mesh").hier_mesh


# ---- capability predicates (parity: operations.cc:690-760) -----------------


def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def xla_built() -> bool:
    """Always true: XLA collectives are the native backend."""
    return True


def tpu_available() -> bool:
    """Whether the default backend drives TPU devices. A backend that
    fails to initialize raises (jax's own error) instead of reading as
    "no TPU": a chip that is present but unusable is not a CPU host."""
    import jax

    return any(d.platform == "tpu" for d in jax.devices())
