#!/usr/bin/env python
"""Capture a jax.profiler trace of a model-zoo train step and summarize
the device-plane op costs (name the single-chip MFU ceiling
operation-by-operation). --model picks any bench.py registry entry
(resnet50/resnet101/vgg16/inception3). Run it through the chip tool; the
default --out is the directory the tool copies back.

Usage: python tools/profile_resnet.py [--model resnet50]
                                      [--batch-size 32] [--steps 5]
                                      [--out chiprun_out/profile]

Writes <out>/<model>_trace_<ts>/ (the raw TB trace dir) and
<out>/<model>_trace_<ts>_summary.md (top ops by device self-time).
"""

import argparse
import glob
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def capture(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import importlib

    import bench as _bench

    import horovod_tpu as hvd
    from horovod_tpu.training import (
        init_train_state, make_train_step, replicate_state, shard_batch)

    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    # Same registry as bench.py --model: trace any of the headline zoo.
    mspec = _bench.MODELS[args.model]
    if args.image_size is None:
        args.image_size = mspec["size"]
    ctor = getattr(importlib.import_module(mspec["module"]), mspec["cls"])
    model = ctor(num_classes=1000, dtype=jnp.bfloat16)
    optimizer = optax.sgd(0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32)
    state = replicate_state(init_train_state(model, optimizer, rng, sample),
                            mesh)

    global_batch = args.batch_size * n
    images = np.random.RandomState(0).rand(
        global_batch, args.image_size, args.image_size, 3).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, 1000, size=(global_batch,)).astype(np.int32)
    images, labels = shard_batch((images, labels), mesh)

    step = make_train_step(model, optimizer, mesh)

    for _ in range(3):  # compile + warmup
        state, loss = step(state, images, labels)
    loss.block_until_ready()

    ts = time.strftime("%Y%m%dT%H%M%S")
    trace_dir = os.path.join(args.out, f"{args.model}_trace_{ts}")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, images, labels)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    img_per_sec = global_batch * args.steps / dt
    return trace_dir, dict(**_bench.device_identity(), model=args.model,
                           batch_size=args.batch_size, steps=args.steps,
                           img_per_sec=round(img_per_sec, 1),
                           step_ms=round(1e3 * dt / args.steps, 2))


def summarize(trace_dir, meta, args):
    """Aggregate XLA op self-times from the captured xplane protobuf."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        # TF is an optional front-end (docs/install.md); losing the
        # summary must not crash the tool AFTER the on-chip capture
        # succeeded — the raw trace dir is still the artifact.
        print(f"summarize skipped (tensorflow unavailable: {e}); "
              f"raw trace kept at {trace_dir}", file=sys.stderr)
        return None

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        print(f"no xplane.pb under {trace_dir}", file=sys.stderr)
        return None
    per_op = defaultdict(float)         # op name -> total self ns
    per_cat = defaultdict(float)        # op category -> total ns
    plane_total = 0.0
    for path in paths:
        xspace = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xspace.ParseFromString(f.read())
        for plane in xspace.planes:
            pn = plane.name.lower()
            # Device planes only ("/device:TPU:0" / "TPU:0") unless the
            # host planes were asked for.
            is_dev = "tpu" in pn or "gpu" in pn
            if not is_dev and not args.include_host:
                continue
            ev_meta = plane.event_metadata
            stats_meta = plane.stat_metadata
            for line in plane.lines:
                ln = line.name.lower()
                # Skip derived lines (steps, framework annotations, and
                # the whole-module spans that would double-count every
                # op); the "XLA Ops" line carries the real timings.
                if "step" in ln or "framework" in ln or "module" in ln:
                    continue
                for ev in line.events:
                    md = ev_meta.get(ev.metadata_id)
                    if md is None:
                        continue
                    dur = ev.duration_ps / 1e3  # ps -> ns
                    name = md.display_name or md.name
                    per_op[name] += dur
                    plane_total += dur
                    cat = ""
                    for st in ev.stats:
                        smd = stats_meta.get(st.metadata_id)
                        if smd is not None and smd.name == "hlo_category":
                            cat = st.str_value
                    if cat:
                        per_cat[cat] += dur
    if not per_op:
        print("no device events parsed", file=sys.stderr)
        return None

    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:args.top]
    lines = [
        f"# {meta.get('model', 'resnet50')} train-step trace — {meta['platform']} "
        f"({meta['device_kind']})",
        "",
        f"Captured {time.strftime('%Y-%m-%d %H:%M:%S')}: "
        f"batch {meta['batch_size']}/chip x {meta['steps']} steps, "
        f"{meta['img_per_sec']} img/s, {meta['step_ms']} ms/step.",
        "",
        f"Total device busy time parsed: {plane_total/1e6:.2f} ms "
        f"across {len(per_op)} distinct ops.",
        "",
        "| rank | op | total ms | % of busy |",
        "|---|---|---|---|",
    ]
    for i, (name, ns) in enumerate(top):
        lines.append(f"| {i+1} | `{name[:80]}` | {ns/1e6:.3f} | "
                     f"{100*ns/plane_total:.1f}% |")
    if per_cat:
        lines += ["", "By HLO category:", "",
                  "| category | total ms | % |", "|---|---|---|"]
        for cat, ns in sorted(per_cat.items(), key=lambda kv: -kv[1]):
            lines.append(f"| {cat} | {ns/1e6:.3f} | "
                         f"{100*ns/plane_total:.1f}% |")
    return "\n".join(lines) + "\n"


def main(argv=None):
    p = argparse.ArgumentParser()
    import bench as _bench

    p.add_argument("--model", default="resnet50",
                   choices=sorted(_bench.MODELS))
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--image-size", type=int, default=None,
                   help="defaults to the model's canonical size")
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--out", default="chiprun_out/profile")
    p.add_argument("--include-host", action="store_true",
                   help="also aggregate host-plane events")
    args = p.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    from tools.compile_cache import enable_compile_cache

    print(f"profile: compile cache at {enable_compile_cache()}",
          file=sys.stderr)
    _bench.require_accelerator()

    trace_dir, meta = capture(args)
    print(json.dumps(meta))
    summary = summarize(trace_dir, meta, args)
    if summary:
        out = trace_dir.rstrip("/") + "_summary.md"
        with open(out, "w") as f:
            f.write(summary)
        print(f"summary -> {out}", file=sys.stderr)
        sys.stderr.write(summary[:2000] + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
