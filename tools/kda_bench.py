#!/usr/bin/env python
"""On-chip check and timing of the KDA scan's kernels (``ops/kda.py``).

Needs the chip (no accelerator is a non-zero exit). At a short length the
kernel pair is held to the recurrence over time in float32 (output and
every gradient); at the cell's length a call of ``kda_fwd`` (with the
states it keeps for the backward pass) and a call of ``kda_bwd`` are timed
apart, each a line of JSON with the plan's heads a grid step: ``device_ms``
is the Mosaic custom call's own time in a profiler trace, what the
benchmark's ``breakdown`` reads per call, ``host_ms`` the host's clock
around the jitted pass (beta's broadcast and the reshapes with it).

``--try-heads 1,2,4`` runs everything again with the plan held to at most
that many heads a step (the bench's own override of ``kda._HEADS``: the
program takes what ``kernel_plan`` gives).

Usage: python tools/kda_bench.py [--chunks 64,128] [--seq-len 16384]
           [--heads 32] [--dim 128] [--iters 5] [--try-heads 1,2,4]
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def operands(seed, b, T, H, K, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (b, T, H, K)) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = jax.random.uniform(ks[3], (b, T, H, K), minval=-5.0, maxval=0.0)
    beta = jax.random.uniform(ks[4], (b, T, H))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def call_ms(fn, args, iters):
    """(host-clock ms, the Mosaic kernels' device ms) a call of the jitted
    ``fn``, compiled before either is read."""
    import jax

    from benchmark import trace_reduce

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    host = 1e3 * (time.perf_counter() - t0) / iters
    with tempfile.TemporaryDirectory() as where:
        jax.profiler.start_trace(where)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        planes = trace_reduce.device_planes(trace_reduce.load(found[0]))
    kernels = trace_reduce.op_events(planes[0][1],
                                     match=trace_reduce.is_mosaic_kernel)
    return host, sum(e[2] for e in kernels) / 1e6 / iters


def passes(kda, chunk):
    """The two passes as ``kda_chunked`` reaches them on the chip, on the
    sums inside a chunk, jitted apart."""
    import jax

    fwd = jax.jit(lambda q, k, v, G, beta: kda._pallas_forward(
        q, k, v, G, beta, chunk, False, True))
    bwd = jax.jit(lambda q, k, v, G, beta, S, do: kda._pallas_backward(
        q, k, v, G, beta, S, do, chunk, False))
    return fwd, bwd


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--chunks", default="64,128")
    parser.add_argument("--seq-len", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--try-heads", default="")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.reference_ling import delta_rule as recurrence
    from horovod_tpu.ops import kda

    if jax.default_backend() != "tpu":
        print("kda_bench: no TPU", file=sys.stderr)
        return 3
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) /  # noqa: E731
                             (jnp.linalg.norm(b) + 1e-30))
    small = operands(1, 1, 512, 4, args.dim, jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(2), small[2].shape)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(recurrence(*a) * weight),
            argnums=(0, 1, 2, 3, 4)))(*small)
    q, k, v, g, beta = operands(3, 1, args.seq_len, args.heads, args.dim,
                                jnp.bfloat16)
    b, T, H, K = q.shape
    the_rule = kda._HEADS
    for at_most in (list(map(int, args.try_heads.split(",")))
                    if args.try_heads else [the_rule[0]]):
        kda._HEADS = tuple(n for n in the_rule if n <= at_most)
        for chunk in map(int, args.chunks.split(",")):
            check(kda, chunk, small, weight, want, rel)
            plan = kda.kernel_plan(H, K, K, chunk, q.dtype)
            G = jnp.cumsum(g.reshape(b, T // chunk, chunk, H, K),
                           axis=2).reshape(b, T, H, K)
            big = (q, k, v, G, beta)
            fwd, bwd = passes(kda, chunk)
            o, S = fwd(*big)
            for name, fn, xs in (("kda_fwd", fwd, big),
                                 ("kda_bwd", bwd, big + (S, o))):
                host, device = call_ms(fn, xs, args.iters)
                print(json.dumps({
                    "chunk": chunk, "call": name, "shape": list(q.shape),
                    "heads_per_step": plan.heads, "device_ms": device,
                    "host_ms": host}), flush=True)
    return 0


def check(kda, chunk, small, weight, want, rel):
    """The kernels' output and gradients against the recurrence's, at the
    plan the shape gets."""
    import jax
    import jax.numpy as jnp

    for dtype in (jnp.float32, jnp.bfloat16):
        cast = [x.astype(dtype) for x in small[:3]] + list(small[3:])
        H, K = cast[0].shape[2:]
        with jax.default_matmul_precision("highest"):
            got = jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(kda.kda_chunked(
                    *a, chunk=chunk).astype(jnp.float32) * weight),
                argnums=(0, 1, 2, 3, 4)))(*cast)
        print(json.dumps({
            "chunk": chunk, "dtype": jnp.dtype(dtype).name,
            "heads_per_step": kda.kernel_plan(H, K, K, chunk, dtype).heads,
            "loss": [float(got[0]), float(want[0])],
            "grad_rel_err": [rel(a.astype(jnp.float32), b)
                             for a, b in zip(got[1], want[1])]}),
            flush=True)


if __name__ == "__main__":
    sys.exit(main())
