#!/usr/bin/env python
"""On-chip check and timing of the KDA scan's kernels (``ops/kda.py``).

Needs the chip (no accelerator is a non-zero exit). At a short length the
kernel pair and the scan over chunks are held to the recurrence over time
in float32 (output and every gradient); at the cell's length the kernels
are timed, forward and forward + backward, a line of JSON each.

Usage: python tools/kda_bench.py [--chunks 64,128] [--seq-len 16384]
           [--heads 32] [--dim 128] [--iters 5]
"""

import argparse
import json
import os
import sys
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


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--chunks", default="64,128")
    parser.add_argument("--seq-len", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--iters", type=int, default=5)
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
    for chunk in map(int, args.chunks.split(",")):
        for dtype in (jnp.float32, jnp.bfloat16):
            cast = [x.astype(dtype) for x in small[:3]] + list(small[3:])
            with jax.default_matmul_precision("highest"):
                got = jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(kda.kda_chunked(
                        *a, chunk=chunk).astype(jnp.float32) * weight),
                    argnums=(0, 1, 2, 3, 4)))(*cast)
            print(json.dumps({
                "chunk": chunk, "dtype": jnp.dtype(dtype).name,
                "loss": [float(got[0]), float(want[0])],
                "grad_rel_err": [rel(a.astype(jnp.float32), b)
                                 for a, b in zip(got[1], want[1])]}),
                flush=True)
        big = operands(3, 1, args.seq_len, args.heads, args.dim,
                       jnp.bfloat16)
        fwd = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk))
        both = jax.jit(jax.grad(
            lambda *a: jnp.sum(kda.kda_chunked(*a, chunk=chunk).astype(
                jnp.float32)), argnums=(0, 1, 2, 3, 4)))
        for name, fn in (("fwd", fwd), ("fwd+bwd", both)):
            jax.block_until_ready(fn(*big))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(*big)
            jax.block_until_ready(out)
            print(json.dumps({
                "chunk": chunk, "phase": name, "shape": list(big[0].shape),
                "ms": 1e3 * (time.perf_counter() - t0) / args.iters}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
