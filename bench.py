#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic throughput (images/sec/chip).

Protocol mirrors the reference's ``examples/pytorch_synthetic_benchmark.py``
(batch 32 per chip, synthetic ImageNet-shaped data, mean over timed
iterations). Baseline for ``vs_baseline``: the reference's published
ResNet-101 tf_cnn_benchmarks number, 1656.82 images/sec on 16 Pascal GPUs
= 103.55 img/s/device (``docs/benchmarks.rst:31-41``; BASELINE.md).

Prints exactly one JSON line, from the one process that ran the step:
every result names the ``platform``, ``device_kind`` and ``device_count``
jax reported there. The measurement path needs the chip — with no
accelerator ``python bench.py`` exits non-zero and prints nothing; it
never stands a CPU run in for a device number. Run it on the chip through
the chip tool (docs/benchmarks.md). Tests import :func:`resnet_bench`
to drive a toy size on the CPU backend on purpose.
"""

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16.0

# Forward GMACs per image at the canonical input size, x2 for the
# FMA-counts-as-2 convention hardware peaks use; a training step
# (fwd + bwd) is conventionally ~3x forward. Used only for the MFU
# field. The model set mirrors the reference's headline benchmark trio
# (docs/benchmarks.rst:8-13: Inception V3 / ResNet / VGG-16) plus the
# ResNet-101 its throughput table quotes (:43).
MODELS = {
    "resnet50": {"fwd_flops": 2 * 4.1e9, "size": 224,
                 "module": "horovod_tpu.models.resnet", "cls": "ResNet50",
                 "s2d": True},
    "resnet101": {"fwd_flops": 2 * 7.6e9, "size": 224,
                  "module": "horovod_tpu.models.resnet",
                  "cls": "ResNet101", "s2d": True},
    "vgg16": {"fwd_flops": 2 * 15.5e9, "size": 224,
              "module": "horovod_tpu.models.vgg", "cls": "VGG16",
              "s2d": False},
    "inception3": {"fwd_flops": 2 * 2.85e9, "size": 299,
                   "module": "horovod_tpu.models.inception",
                   "cls": "InceptionV3", "s2d": False},
}

# Dense bf16 peak per chip, by device_kind substring (lowercase match).
# Source: Google Cloud TPU documentation, per-generation system pages.
PEAK_FLOPS_BY_KIND = [
    ("v6", 918e12),     # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
]


def _peak_flops(device_kind):
    """Peak dense bf16 FLOP/s of one chip. A device that is not in the
    table is an error, not a default: a utilization against a guessed
    peak is not a measurement."""
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
        f"it to PEAK_FLOPS_BY_KIND with its source")


def device_identity():
    """The device as jax reports it in THIS process — the one that runs
    the step — for every result to carry."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_accelerator():
    """The measurement path's gate: a CPU backend is a failure, never a
    stand-in. Returns :func:`device_identity` on an accelerator."""
    ident = device_identity()
    if ident["platform"] == "cpu":
        raise SystemExit(
            "bench: no accelerator (jax platform is 'cpu'). A device "
            "number comes only from a chip run; nothing was measured.")
    return ident


def _build_parser():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="resnet",
                        choices=["resnet", "zero"],
                        help="'resnet': the headline synthetic-throughput "
                             "benchmark (default). 'zero': the ZeRO "
                             "stage-1/2/3 memory+throughput A/B "
                             "(docs/zero.md) — per-device live-buffer "
                             "bytes by jax.live_arrays accounting, "
                             "analytic wire bytes/step, steps/sec")
    parser.add_argument("--zero-stage", type=int, default=None,
                        choices=[1, 2, 3],
                        help="with --workload zero: bench only this "
                             "stage (default: all three, the stage "
                             "1->3 memory curve)")
    parser.add_argument("--zero-devices", type=int, default=4,
                        help="with --workload zero: data-parallel world "
                             "size d, the first d devices jax reports")
    parser.add_argument("--model", default="resnet50",
                        choices=sorted(MODELS),
                        help="benchmark model (the reference's headline "
                             "trio + ResNet-101); the driver-facing "
                             "default stays resnet50")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-warmup", type=int, default=5)
    parser.add_argument("--num-iters", type=int, default=30)
    parser.add_argument("--image-size", type=int, default=None,
                        help="defaults to the model's canonical size "
                             "(224; 299 for inception3)")
    parser.add_argument("--fence-each", action="store_true",
                        help="fence every timed iteration and report "
                             "steps/sec with a 95%% CI (regression-canary "
                             "mode; trades pipelining for variance data)")
    parser.add_argument("--space-to-depth", action="store_true",
                        help="use the MXU space-to-depth stem (exact "
                             "re-tiling of the 7x7/s2 stem conv; "
                             "models/resnet.py) — A/B flag for on-chip "
                             "MFU work")
    parser.add_argument("--bucket-mb", type=float, default=None,
                        help="tensor-fusion v2 bucket cap in MB for the "
                             "gradient AllReduce (backward-order bucketed "
                             "fusion; 0 forces monolithic). Unset: follow "
                             "HOROVOD_FUSION_THRESHOLD, monolithic when "
                             "that is unset too. The effective config is "
                             "recorded in the emitted JSON either way")
    parser.add_argument("--compression", default=None,
                        choices=["none", "fp16", "bf16", "ef16"],
                        help="on-wire gradient compression for the "
                             "gradient AllReduce (common/compression.py; "
                             "docs/compression.md). Unset: follow "
                             "HOROVOD_COMPRESSION, uncompressed when "
                             "that is unset too. The effective mode and "
                             "wire bytes/step are recorded in the "
                             "emitted JSON either way")
    parser.add_argument("--fault-spec", default=None,
                        help="HOROVOD_FAULT_SPEC for the benched worker "
                             "(docs/fault-injection.md): chaos-bench the "
                             "recovery overhead, e.g. "
                             "'ring.exec:kind=delay_ms:ms=5'. The spec "
                             "is recorded in the emitted JSON so a "
                             "fault-injected number can never be "
                             "mistaken for a clean one")
    return parser


# ---- local-leg transport bench (--local-leg) -------------------------------
#
# Host-plane A/B: the SAME hierarchical world (2 simulated hosts x
# local_size ranks, round-robin placement) timed over fused allreduces
# with the intra-host legs on loopback TCP vs the shm transport
# (docs/shm-transport.md). Emits one JSON line with us/MB per transport
# so BENCH artifacts carry the shm-vs-loopback line; the traffic
# counters prove which plane moved the bytes.

def _local_leg_worker(argv):
    rank, port, size, hosts, nbytes, iters = (int(a) for a in argv)
    import numpy as np

    from horovod_tpu.common import native as hn

    core = hn.NativeCore()
    assert core.available, "native runtime unavailable"
    ok = core.init(rank=rank, size=size, local_rank=rank // hosts,
                   local_size=size // hosts, cross_rank=rank % hosts,
                   cross_size=hosts, coordinator_addr="127.0.0.1",
                   coordinator_port=port, my_host="127.0.0.1",
                   cycle_time_ms=1.0, fusion_threshold=64 << 20,
                   cache_capacity=64, stall_warning_sec=120.0,
                   stall_shutdown_sec=0.0, stall_check_enabled=False,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "host-plane only"))
    assert ok, "native init failed"
    count = nbytes // 4
    buf = np.zeros(count, np.float32)

    def allreduce(name):
        h = core.enqueue(name, hn.OP_ALLREDUCE, 1, 7, buf.shape,
                         data_ptr=buf.ctypes.data,
                         output_ptr=buf.ctypes.data, plane=hn.PLANE_HOST)
        r, err = core.wait(h)
        assert r == 1, err

    if rank == 0:
        core.set_hier_flags(3)
    for i in range(3):
        allreduce(f"warm.{i}")
    t0 = time.perf_counter()
    for i in range(iters):
        allreduce(f"leg.{i}")
    dt = time.perf_counter() - t0
    traffic = {"seconds": dt, "shm": core.shm_active(),
               "local_bytes": core.ring_local_bytes(),
               "cross_bytes": core.ring_cross_bytes(),
               "shm_bytes": core.ring_shm_bytes()}
    print("LLBENCH " + json.dumps({"rank": rank, **traffic}), flush=True)
    core.shutdown()
    print(f"LLWORKER_{rank}_OK", flush=True)
    return 0


def _local_leg_world(size, hosts, nbytes, iters, shm):
    import socket as _socket

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    # Host-plane workers compute nothing on a device; keep them off the
    # chip, which belongs to one process at a time.
    env = dict(os.environ, HOROVOD_SHM="1" if shm else "0",
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--local-leg-worker",
         str(r), str(port), str(size), str(hosts), str(nbytes),
         str(iters)], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(size)]
    per_rank = []
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=300)
            assert p.returncode == 0 and f"LLWORKER_{r}_OK" in out, \
                f"local-leg rank {r} failed:\n{out}"
            for line in out.splitlines():
                if line.startswith("LLBENCH "):
                    per_rank.append(json.loads(line[len("LLBENCH "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    seconds = max(d["seconds"] for d in per_rank)
    agg = {k: sum(d[k] for d in per_rank)
           for k in ("local_bytes", "cross_bytes", "shm_bytes")}
    moved_mb = (agg["local_bytes"] + agg["shm_bytes"]) / 1e6
    return {
        "transport": "shm" if shm else "tcp",
        "shm_active_ranks": sum(1 for d in per_rank if d["shm"]),
        "seconds": round(seconds, 4),
        "us_per_local_mb": (round(seconds * 1e6 / moved_mb, 2)
                            if moved_mb > 0 else None),
        **agg,
    }


def local_leg_bench(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--size", type=int, default=4,
                        help="world size (2 simulated hosts x size/2)")
    parser.add_argument("--payload-mb", type=float, default=4.0,
                        help="fused allreduce payload per iteration")
    parser.add_argument("--num-iters", type=int, default=20)
    args = parser.parse_args(argv)
    size = max(4, args.size - args.size % 2)
    nbytes = int(args.payload_mb * (1 << 20))
    rows = [
        _local_leg_world(size, 2, nbytes, args.num_iters, shm=False),
        _local_leg_world(size, 2, nbytes, args.num_iters, shm=True),
    ]
    tcp, shm = rows
    result = {
        "metric": "local_leg_us_per_mb",
        "value": shm["us_per_local_mb"],
        "unit": "us/MB (intra-host leg, shm)",
        "baseline_tcp_us_per_mb": tcp["us_per_local_mb"],
        "speedup_vs_loopback_tcp": (
            round(tcp["seconds"] / shm["seconds"], 3)
            if shm["seconds"] > 0 else None),
        "world": {"size": size, "hosts": 2, "payload_mb": args.payload_mb,
                  "iters": args.num_iters},
        "transports": rows,
    }
    print(json.dumps(result))
    return 0


# ---- cross-leg transport bench (--cross-leg) -------------------------------
#
# Host-plane A/B, the --local-leg sibling for the OTHER half of the
# traffic model: the SAME hierarchical world (2 simulated hosts x
# local_size ranks, round-robin placement, two-level dispatch on) timed
# over fused allreduces with the cross-host leader leg on a single
# blocking TCP socket vs striped multi-socket + pipelined chunking
# (docs/cross-transport.md). Emits one JSON line with us/MB of cross
# traffic per mode; the counters prove cross_bytes is byte-identical
# across modes and a per-rank CRC proves the collective results are
# bitwise equal (uint32-view identity) — striping changes the carrier,
# never the math.

def _cross_leg_worker(argv):
    rank, port, size, hosts, nbytes, iters = (int(a) for a in argv)
    import zlib

    import numpy as np

    from horovod_tpu.common import native as hn

    core = hn.NativeCore()
    assert core.available, "native runtime unavailable"
    ok = core.init(rank=rank, size=size, local_rank=rank // hosts,
                   local_size=size // hosts, cross_rank=rank % hosts,
                   cross_size=hosts, coordinator_addr="127.0.0.1",
                   coordinator_port=port, my_host="127.0.0.1",
                   cycle_time_ms=1.0, fusion_threshold=64 << 20,
                   cache_capacity=64, stall_warning_sec=120.0,
                   stall_shutdown_sec=0.0, stall_check_enabled=False,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "host-plane only"))
    assert ok, "native init failed"
    count = nbytes // 4
    # Deterministic small-int inputs, exactly representable in fp32: the
    # reduction is exact, so the CRC must agree bit-for-bit across
    # transports AND across runs.
    base = (np.arange(count) % 13).astype(np.float32)

    def allreduce(name):
        buf = base * (rank + 1)
        h = core.enqueue(name, hn.OP_ALLREDUCE, 1, 7, buf.shape,
                         data_ptr=buf.ctypes.data,
                         output_ptr=buf.ctypes.data, plane=hn.PLANE_HOST)
        r, err = core.wait(h)
        assert r == 1, err
        return buf

    if rank == 0:
        core.set_hier_flags(3)
    for i in range(3):
        out = allreduce(f"warm.{i}")
    c0 = core.ring_cross_bytes()
    s0 = core.ring_stripe_bytes()
    n0 = core.ring_cross_ns()
    t0 = time.perf_counter()
    for i in range(iters):
        out = allreduce(f"leg.{i}")
    dt = time.perf_counter() - t0
    row = {"rank": rank, "seconds": dt,
           "cross_bytes": core.ring_cross_bytes() - c0,
           "stripe_bytes": core.ring_stripe_bytes() - s0,
           # Leg-local clock: time inside the leader exchanges alone —
           # the honest A/B on a box where end-to-end iteration time is
           # dominated by fusion copies and idle members' yield-spins.
           "cross_leg_ns": core.ring_cross_ns() - n0,
           "stripes": core.ring_stripe_count(),
           "result_crc": zlib.crc32(out.tobytes())}
    print("CLBENCH " + json.dumps(row), flush=True)
    core.shutdown()
    print(f"CLWORKER_{rank}_OK", flush=True)
    return 0


def _cross_leg_world(size, hosts, nbytes, iters, stripes, chunk_bytes):
    import socket as _socket

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    # Both modes ride the shm local legs (docs/shm-transport.md): the
    # post-PR 7 production shape, where every remaining wire byte is
    # cross-host — so the A/B isolates the leader leg under test
    # instead of measuring loopback-TCP member traffic.
    env = dict(os.environ, HOROVOD_STRIPES=str(stripes),
               HOROVOD_CHUNK_BYTES=str(chunk_bytes),
               HOROVOD_SHM="1", JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--cross-leg-worker",
         str(r), str(port), str(size), str(hosts), str(nbytes),
         str(iters)], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(size)]
    per_rank = []
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=300)
            assert p.returncode == 0 and f"CLWORKER_{r}_OK" in out, \
                f"cross-leg rank {r} failed:\n{out}"
            for line in out.splitlines():
                if line.startswith("CLBENCH "):
                    per_rank.append(json.loads(line[len("CLBENCH "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    seconds = max(d["seconds"] for d in per_rank)
    cross = sum(d["cross_bytes"] for d in per_rank)
    stripe = sum(d["stripe_bytes"] for d in per_rank)
    # The leg metric sums over the leaders (members contribute 0 ns and
    # 0 cross bytes): total leader-leg time per MB of cross payload.
    leg_s = sum(d["cross_leg_ns"] for d in per_rank) / 1e9
    cross_mb = cross / 1e6
    return {
        "transport": "striped" if stripes > 1 else "single-socket",
        "stripes": max(d["stripes"] for d in per_rank),
        "seconds": round(seconds, 4),
        "cross_leg_seconds": round(leg_s, 4),
        "us_per_cross_mb": (round(leg_s * 1e6 / cross_mb, 2)
                            if cross_mb > 0 else None),
        "cross_bytes": cross,
        "stripe_bytes": stripe,
        "result_crcs": {str(d["rank"]): d["result_crc"]
                        for d in per_rank},
    }


def cross_leg_bench(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--size", type=int, default=4,
                        help="world size (2 simulated hosts x size/2)")
    parser.add_argument("--payload-mb", type=float, default=8.0,
                        help="fused allreduce payload per iteration "
                             "(8 MB+ keeps the leader leg well above "
                             "the tree cutoff and long enough to "
                             "pipeline)")
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--stripes", type=int, default=8,
                        help="stripe count for the striped mode "
                             "(HOROVOD_STRIPES)")
    parser.add_argument("--chunk-kb", type=int, default=1024,
                        help="pipeline chunk (HOROVOD_CHUNK_BYTES) for "
                             "both modes; 1 MiB won the sweep on this "
                             "box (loopback pays per-piece syscalls; "
                             "real NICs may prefer smaller chunks for "
                             "deeper pipelining)")
    args = parser.parse_args(argv)
    size = max(4, args.size - args.size % 2)
    nbytes = int(args.payload_mb * (1 << 20))
    chunk = args.chunk_kb * 1024
    rows = [
        _cross_leg_world(size, 2, nbytes, args.num_iters, stripes=1,
                         chunk_bytes=chunk),
        _cross_leg_world(size, 2, nbytes, args.num_iters,
                         stripes=args.stripes, chunk_bytes=chunk),
    ]
    single, striped = rows
    result = {
        "metric": "cross_leg_us_per_mb",
        "value": striped["us_per_cross_mb"],
        "unit": "us/MB (cross-host leader leg, striped+pipelined)",
        "baseline_single_socket_us_per_mb": single["us_per_cross_mb"],
        # Leg-over-leg: time INSIDE the leader exchanges, single-socket
        # vs striped+pipelined — what the transport change actually
        # touches. End-to-end wall clock rides along per transport row.
        "speedup_vs_single_socket": (
            round(single["cross_leg_seconds"] /
                  striped["cross_leg_seconds"], 3)
            if striped["cross_leg_seconds"] > 0 else None),
        "wall_clock_speedup": (
            round(single["seconds"] / striped["seconds"], 3)
            if striped["seconds"] > 0 else None),
        # The acceptance invariants, recorded so a BENCH artifact can
        # never silently carry a divergent run: payload accounting is
        # carrier-independent, and the reduced tensors are bitwise
        # equal on every rank.
        "cross_bytes_match": single["cross_bytes"] ==
        striped["cross_bytes"],
        "results_match": single["result_crcs"] == striped["result_crcs"],
        "world": {"size": size, "hosts": 2,
                  "payload_mb": args.payload_mb,
                  "iters": args.num_iters, "stripes": args.stripes,
                  "chunk_bytes": chunk, "local_transport": "shm"},
        "transports": rows,
    }
    print(json.dumps(result))
    return 0


def resnet_bench(args):
    """Run the ResNet-protocol workload in this process; returns the
    result dict (``main`` prints it). ``args`` is a parsed
    :func:`_build_parser` namespace."""
    if args.image_size is None:
        args.image_size = MODELS[args.model]["size"]
    # At least one timed iteration: the loop variable feeds the
    # completion fence and the throughput numerator.
    args.num_iters = max(1, args.num_iters)
    if args.fault_spec:
        # Read lazily at the first fault point (common/faults.py).
        os.environ["HOROVOD_FAULT_SPEC"] = args.fault_spec

    t_start = time.perf_counter()

    def mark(msg):
        # Progress breadcrumbs on stderr: a run killed from outside
        # still shows the last phase it reached.
        print("bench: %s (+%.0fs)" % (msg, time.perf_counter() - t_start),
              file=sys.stderr, flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.training import (
        init_train_state, make_train_step, replicate_state, shard_batch)

    mark("imports done")
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    ident = device_identity()
    mark(f"backend init done ({ident})")

    # Registry-driven dispatch: a MODELS entry fully describes the model
    # (module/class/s2d support), so adding one cannot silently fall
    # through to the wrong constructor.
    import importlib

    spec = MODELS[args.model]
    ctor = getattr(importlib.import_module(spec["module"]), spec["cls"])
    kwargs = {"num_classes": 1000, "dtype": jnp.bfloat16}
    if spec["s2d"]:
        kwargs["space_to_depth_stem"] = args.space_to_depth
    model = ctor(**kwargs)
    optimizer = optax.sgd(0.01, momentum=0.9)

    # On-wire compression: --compression wins, else HOROVOD_COMPRESSION
    # ("auto"), else uncompressed. Resolved ONCE, before the state is
    # built, so error-feedback residual structure matches the step.
    from horovod_tpu.common.compression import resolve_compression

    if args.compression is not None:
        comp = resolve_compression(args.compression)
        comp_source = "flag"
    else:
        comp = resolve_compression("auto")
        comp_source = ("env" if os.environ.get("HOROVOD_COMPRESSION")
                       is not None else "unset")

    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32)
    state = replicate_state(init_train_state(model, optimizer, rng, sample,
                                             compression=comp),
                            mesh)

    global_batch = args.batch_size * n
    images = np.random.RandomState(0).rand(
        global_batch, args.image_size, args.image_size, 3).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, 1000, size=(global_batch,)).astype(np.int32)
    # Host arrays go straight to their shards: jnp.asarray first would
    # land the whole global batch on device 0 and copy it out again.
    images, labels = shard_batch((images, labels), mesh)

    # Tensor-fusion v2: --bucket-mb wins, else HOROVOD_FUSION_THRESHOLD
    # ("auto"), else monolithic. The effective config rides the JSON so
    # the bench trajectory can attribute wins to the fusion setting.
    from horovod_tpu.common.fusion import (
        describe_plan, plan_buckets_for, resolve_bucket_cap)

    if args.bucket_mb is not None:
        bucket_cap = int(args.bucket_mb * 1024 * 1024) or None
        cap_source = "flag"
    else:
        bucket_cap = resolve_bucket_cap("auto")
        # Attribute correctly: "auto" may resolve from the env var OR
        # from an autotuner-published threshold in the live config.
        if bucket_cap is None:
            cap_source = "unset"
        elif os.environ.get("HOROVOD_FUSION_THRESHOLD") is not None:
            cap_source = "env"
        else:
            cap_source = "autotune"
    from horovod_tpu.common.fusion import leaf_wire_nbytes

    param_leaves = jax.tree_util.tree_leaves(state.params)
    fusion_cfg = {
        "bucket_cap_bytes": bucket_cap,
        "source": cap_source,
        **describe_plan(plan_buckets_for(param_leaves, bucket_cap,
                                         comp)),
    }
    compression_cfg = {
        "mode": comp.name if comp is not None else "none",
        "source": comp_source,
        # Gradient bytes one chip moves into the allreduce per step at
        # the effective wire dtype (fp32 for uncompressed bf16/fp16
        # models — the accumulation wire; leaf_wire_nbytes delegates
        # through the error-feedback wrapper to its inner wire).
        "wire_bytes_per_step": sum(
            leaf_wire_nbytes(l, comp) for l in param_leaves),
    }
    mark(f"fusion config: {fusion_cfg}")
    mark(f"compression config: {compression_cfg}")

    step = make_train_step(model, optimizer, mesh,
                           bucket_cap_bytes=bucket_cap,
                           compression=comp)

    # The final loss depends on every prior step through the donated
    # state chain, so blocking on it fences the whole run.
    mark("state initialized; compiling + warmup")
    for _ in range(args.num_warmup):
        state, loss = step(state, images, labels)
    if args.num_warmup > 0:
        loss.block_until_ready()
    mark("warmup fenced; timing")

    step_times = []
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        t1 = time.perf_counter()
        state, loss = step(state, images, labels)
        if args.fence_each:
            loss.block_until_ready()
            step_times.append(time.perf_counter() - t1)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    mark(f"timed {args.num_iters} iters in {dt:.1f}s")

    img_per_sec = global_batch * args.num_iters / dt
    img_per_sec_per_chip = img_per_sec / n

    result = {
        "metric": f"{args.model}_images_per_sec_per_chip",
        "value": round(img_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        **ident,
        # The only per-device throughput the reference publishes is
        # ResNet-101 tf_cnn_benchmarks (103.55 img/s/device); a
        # cross-model ratio against it would be meaningless, so
        # vs_baseline is emitted for the resnets only.
        "vs_baseline": (round(
            img_per_sec_per_chip / BASELINE_IMG_PER_SEC_PER_DEVICE, 3)
            if args.model.startswith("resnet") else None),
        "fusion": fusion_cfg,
        "compression": compression_cfg,
        # Workload identity rides the result: without it a batch-128 or
        # space-to-depth A/B run is indistinguishable from the headline
        # batch-32 protocol.
        "workload": {
            "model": args.model,
            "batch_size": args.batch_size,
            "image_size": args.image_size,
            # Effective value: only the resnets have an s2d stem.
            "space_to_depth": bool(args.space_to_depth) and spec["s2d"],
            "fence_each": bool(args.fence_each),
            "num_iters": args.num_iters,
        },
    }
    # Conv FLOPs scale ~quadratically with input size; scale the
    # canonical-size figure so a non-canonical --image-size run doesn't
    # overstate MFU.
    train_flops = (3 * spec["fwd_flops"]
                   * (args.image_size / spec["size"]) ** 2)
    result["mfu"] = round(img_per_sec_per_chip * train_flops
                          / _peak_flops(ident["device_kind"]), 4)
    if args.fault_spec:
        # A fault-injected number can never be mistaken for a clean one.
        result["fault_spec"] = args.fault_spec
    # Host data-plane traffic shape (docs/hierarchical.md): the
    # local/cross byte split plus the effective two-level dispatch, read
    # AFTER the timed loop so the counters cover the run. Zeros/False on
    # a pure-XLA single-process bench (no host ring) — the fields still
    # ride the JSON so every BENCH artifact records which plane moved
    # the bytes and whether the hierarchical path was on.
    traffic = hvd.ring_traffic()
    result["ring_local_bytes"] = traffic["local_bytes"]
    result["ring_cross_bytes"] = traffic["cross_bytes"]
    result["ring_shm_bytes"] = traffic["shm_bytes"]
    # The transport that carried the intra-host legs (docs/
    # shm-transport.md): "shm" when this rank's segment was live, else
    # the TCP PeerLink fallback/default.
    result["local_transport"] = "shm" if traffic["shm"] else "tcp"
    result["host_hierarchical"] = {
        "allreduce": traffic["hierarchical_allreduce"],
        "allgather": traffic["hierarchical_allgather"],
        "tuned": traffic["tuned"],
    }
    # The FULL unified metrics snapshot (docs/metrics.md): python-plane
    # counters + the native registry (latency histograms, straggler
    # state). Read after the timed loop, like the traffic split, so the
    # BENCH artifact carries the run's whole latency distribution —
    # not just the throughput headline.
    result["metrics"] = hvd.metrics()
    if step_times:
        # Per-step rates + a 95% CI (the reference benchmark's
        # mean +- 1.96*std protocol, pytorch_synthetic_benchmark.py:115).
        rates = [1.0 / t for t in step_times]
        mean = sum(rates) / len(rates)
        var = sum((r - mean) ** 2 for r in rates) / len(rates)
        result["steps_per_sec"] = round(mean, 4)
        result["steps_per_sec_ci95"] = round(
            1.96 * var ** 0.5 / len(rates) ** 0.5, 4)
    hvd.shutdown()
    return result


# ---- ZeRO stage memory/throughput bench (--workload zero) ------------------
#
# Stage-1 -> 2 -> 3 A/B on a d-device data-parallel world (the first d
# devices jax reports, one stage after another in this process).
# Reports, per stage:
#
#  - live_bytes_per_device_peak: jax.live_arrays() accounting on device 0,
#    sampled at every eager boundary (post-init and after each step) —
#    the persistent watermark the stages actually move. Stage 1's extra
#    full-gradient buffer is a *transient inside* the compiled program
#    (invisible to live_arrays); it is reported analytically as
#    transient_full_grad_bytes and proven structurally by the jaxpr tests
#    (tests/test_zero.py: stage 2 has no full-size psum output).
#  - state_bytes_per_device: the ZeroTrainState leaves alone (the
#    params+grads+state curve docs/zero.md tabulates; with the f32 SGD
#    workload stage3/stage1 -> 1/(d+1)).
#  - wire_bytes_per_step_per_device: analytic ring model — stage 1 pays
#    an allreduce (2(d-1)/d) + gather, stage 2 a reduce-scatter + gather
#    ((d-1)/d each), stage 3 a reduce-scatter + TWO gathers (forward +
#    backward re-gather).
#  - steps_per_sec over the timed iterations.

def _zero_stage(stage, devs, batch_size, num_warmup, num_iters,
                hidden=1024, layers=4):
    import jax
    import numpy as np
    import optax
    import flax.linen as nn
    from jax.sharding import Mesh

    from horovod_tpu.common.state import AXIS_GLOBAL
    from horovod_tpu.zero import init_zero_train_state, make_zero_train_step

    d = len(devs)
    mesh = Mesh(np.array(devs), (AXIS_GLOBAL,))

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            for _ in range(layers):
                x = nn.relu(nn.Dense(hidden)(x))
            return nn.Dense(16)(x)

    model = MLP()
    # Plain f32 SGD keeps the memory model crisp: no optimizer moments,
    # so per-device state is exactly params(+masters) and the
    # stage3/stage1 ratio lands at 1/(d+1) (docs/zero.md memory table).
    optimizer = optax.sgd(1e-3)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch_size, hidden))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch_size,), 0, 16)

    dev0 = devs[0]

    def dev_bytes(arrs):
        total = 0
        for a in arrs:
            if a.is_deleted():
                continue
            for s in a.addressable_shards:
                if s.device == dev0:
                    total += int(s.data.size) * s.data.dtype.itemsize
        return total

    state = init_zero_train_state(model, optimizer, rng, x[:1], mesh,
                                  zero_stage=stage)
    step = make_zero_train_step(model, optimizer, mesh, zero_stage=stage)
    peak = dev_bytes(jax.live_arrays())
    for _ in range(num_warmup):
        state, loss = step(state, x, y)
        loss.block_until_ready()
        peak = max(peak, dev_bytes(jax.live_arrays()))
    t0 = time.perf_counter()
    for _ in range(num_iters):
        state, loss = step(state, x, y)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    peak = max(peak, dev_bytes(jax.live_arrays()))

    state_bytes = dev_bytes(
        [l for l in jax.tree_util.tree_leaves(state)
         if isinstance(l, jax.Array)])
    padded = int(state.pshard.shape[0])
    ring = (d - 1) / d
    payload = padded * 4  # fp32 wire, uncompressed
    reduce_leg = payload * ring * (2 if stage == 1 else 1)
    gather_leg = payload * ring * (2 if stage == 3 else 1)
    return {
        "stage": stage,
        "live_bytes_per_device_peak": peak,
        "state_bytes_per_device": state_bytes,
        "transient_full_grad_bytes": (payload if stage == 1
                                      else payload // d),
        "wire_bytes_per_step_per_device": int(reduce_leg + gather_leg),
        "steps_per_sec": round(num_iters / dt, 3),
        "params_padded_elems": padded,
        "loss": round(float(loss), 6),
    }


def zero_bench(args):
    import jax

    stages = [args.zero_stage] if args.zero_stage else [1, 2, 3]
    d = args.zero_devices
    devs = jax.devices()[:d]
    if len(devs) != d:
        raise SystemExit(f"bench: --zero-devices {d} but jax reports "
                         f"{len(jax.devices())} device(s)")
    rows = [_zero_stage(s, devs, args.batch_size, args.num_warmup,
                        args.num_iters) for s in stages]
    by = {row["stage"]: row for row in rows}
    ratio = None
    if 1 in by and 3 in by and by[1]["state_bytes_per_device"]:
        ratio = round(by[3]["state_bytes_per_device"]
                      / by[1]["state_bytes_per_device"], 4)
    return {
        "metric": "zero_stage3_vs_stage1_state_bytes",
        "value": ratio,
        "unit": "per-device live param+grad+state bytes, stage3/stage1",
        "expected_ratio": round(1.0 / (d + 1), 4),
        **device_identity(),
        "world": {"devices": d, "batch_size": args.batch_size,
                  "warmup": args.num_warmup, "iters": args.num_iters},
        "stages": rows,
    }


def main(argv):
    # The host-plane loopback A/Bs and their workers: no device, no jax.
    modes = {"--local-leg-worker": _local_leg_worker,
             "--local-leg": local_leg_bench,
             "--cross-leg-worker": _cross_leg_worker,
             "--cross-leg": cross_leg_bench}
    if argv and argv[0] in modes:
        return modes[argv[0]](argv[1:])

    args = _build_parser().parse_args(argv)
    from tools.compile_cache import enable_compile_cache

    print(f"bench: compile cache at {enable_compile_cache()}",
          file=sys.stderr)
    require_accelerator()
    result = (zero_bench(args) if args.workload == "zero"
              else resnet_bench(args))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
