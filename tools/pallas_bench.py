#!/usr/bin/env python
"""On-chip Pallas flash-attention microbench: Mosaic-compiled kernels
(forward AND backward) vs the XLA attention path, with a numerics check
against the XLA oracle on the same device.

The kernels' lowering, VMEM fit and time on real hardware, not
interpreted numerics: it needs the chip (no accelerator is a non-zero
exit). Prints the device line, then one JSON line per (seq_len, phase).

Usage: python tools/pallas_bench.py [--seq-lens 2048,4096] [--iters 20]
       python tools/pallas_bench.py --kind bwd --seq-lens 8192 --batch 2 \
           --heads 32 --kv-heads 4 --dim 128 [--window 2048]

       python tools/pallas_bench.py --kind gmm [--gmm-cells olmoe,zaya]

``--kind bwd`` times the backward alone at a shape: the fused kernel
``flash_bwd`` beside the two passes ``flash_dq`` + ``flash_dkv`` on the
same operands, with ``kernel_plan``'s plan of each and the largest
difference between their gradients.

``--kind gmm`` times the expert layer's grouped matmuls alone
(``ops/grouped_matmul.py``: the product, the row gradient, ``tgmm``) at
the four expert cells' shapes and the token sums', beside jax's
``megablox`` at the tile the layer gave it until PR 41, under group sizes
balanced to the row, balanced within 6 %, skewed as a seeded router's
and one group of every row; with each plan, the strips
``visited_work`` counts, and the largest difference between the two
kernels' results.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _make_qkv(T, batch, heads, dim):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    shape = (batch, T, heads, dim)
    mk = lambda: jnp.asarray(rng.randn(*shape), jnp.bfloat16)  # noqa: E731
    return mk(), mk(), mk()


def _make_fns(use_pallas, causal, window=None):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_attention import flash_attention

    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, use_pallas=use_pallas, window=window))

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, use_pallas=use_pallas, window=window
        ).astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return fwd, bwd


def _clock(fn, iters, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _build_xla_cache(T, iters, batch, heads, dim, causal=True,
                     window=None):
    """Run the block-size-invariant XLA baseline once: oracle outputs and
    grads for the numerics check plus fwd/bwd timings. Built separately
    from :func:`bench_one` so a Pallas failure (VMEM overflow on one
    sweep config) can't discard the most expensive part of the run."""
    import numpy as np

    q, k, v = _make_qkv(T, batch, heads, dim)
    x_fwd, x_bwd = _make_fns(False, causal, window)
    return {
        "out": np.asarray(x_fwd(q, k, v), np.float32),
        "grads": [np.asarray(g, np.float32) for g in x_bwd(q, k, v)],
        "ms": {"fwd": _clock(x_fwd, iters, q, k, v),
               "bwd": _clock(x_bwd, iters, q, k, v)},
    }


def bench_one(T, iters, batch, heads, dim, causal=True, xla_cache=None,
              window=None):
    """Mosaic vs XLA at ``kernel_plan``'s grid step. ``xla_cache`` — a
    dict from :func:`_build_xla_cache` — skips re-running the
    block-size-invariant XLA baseline (timings AND the numerics-oracle
    outputs/grads; the sweep reuses both)."""
    import numpy as np

    q, k, v = _make_qkv(T, batch, heads, dim)
    p_fwd, p_bwd = _make_fns(True, causal, window)

    if xla_cache is None:
        xla_cache = _build_xla_cache(T, iters, batch, heads, dim, causal,
                                     window)

    # Numerics: Mosaic vs the XLA oracle on the SAME device.
    po = np.asarray(p_fwd(q, k, v), np.float32)
    fwd_maxerr = float(np.max(np.abs(po - xla_cache["out"])))
    pg = p_bwd(q, k, v)
    bwd_maxerr = max(
        float(np.max(np.abs(np.asarray(a, np.float32) - b)))
        for a, b in zip(pg, xla_cache["grads"]))

    rows = []
    for phase, pf in (("fwd", p_fwd), ("bwd", p_bwd)):
        p_ms = _clock(pf, iters, q, k, v)
        x_ms = xla_cache["ms"][phase]
        rows.append({
            "seq_len": T, "phase": phase, "batch": batch, "heads": heads,
            "head_dim": dim, "causal": causal, "window": window,
            "pallas_ms": round(p_ms, 3), "xla_ms": round(x_ms, 3),
            "speedup": round(x_ms / p_ms, 2),
            "maxerr_vs_xla": round(
                fwd_maxerr if phase == "fwd" else bwd_maxerr, 4),
        })
    return rows, xla_cache


def sweep_blocks(T, iters, batch, heads, dim):
    """Time the Mosaic kernels across ``kernel_plan``'s sub-tile cap
    (``_TILE_CAP``: the [t, t] score tile of the in-kernel walk), to
    check its choice per chip generation. Fresh jit wrappers per config
    re-trace with the patched cap."""
    import horovod_tpu.ops.pallas_attention as pa

    cap = pa._TILE_CAP
    # Block-size-invariant: built once up front (before any Pallas config
    # can fail), reused across every config.
    xla_cache = _build_xla_cache(T, iters, batch, heads, dim)
    try:
        for tile in (128, 256, 512, 1024):
            pa._TILE_CAP = tile
            try:
                rows, xla_cache = bench_one(T, iters, batch, heads, dim,
                                            xla_cache=xla_cache)
            except Exception as e:  # VMEM overflow etc.: report, go on
                print(json.dumps({"seq_len": T, "tile": tile,
                                  "error": str(e)[:200]}))
                continue
            for row in rows:
                row["tile"] = tile
                print(json.dumps(row))
                sys.stdout.flush()
    finally:
        pa._TILE_CAP = cap


def bench_bwd(T, iters, batch, heads, kv_heads, dim, window=None):
    """The backward on merged operands, the fused kernel beside the two
    passes (which a budget of nothing leaves ``_pallas_bwd``), lse and
    delta from the forward kernel: one JSON-ready row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu.ops.pallas_attention as pa

    rng = np.random.RandomState(0)
    mk = lambda h: jnp.asarray(                                # noqa: E731
        rng.randn(batch * h, T, dim), jnp.bfloat16)
    q, k, v, do = mk(heads), mk(kv_heads), mk(kv_heads), mk(heads)
    offs = jnp.zeros((2,), jnp.int32)

    @jax.jit
    def residuals(q, k, v, do):
        o, lse = pa._flash_forward(q, k, v, offs, True, False, "train",
                                   window=window)
        return lse, jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1, keepdims=True)

    lse, delta = residuals(q, k, v, do)
    group = heads // kv_heads
    row = {"seq_len": T, "phase": "bwd_kernels", "batch": batch,
           "heads": heads, "kv_heads": kv_heads, "head_dim": dim,
           "window": window, "bwd_vmem_budget": pa.BWD_VMEM_BUDGET}
    budget, grads = pa.BWD_VMEM_BUDGET, {}
    try:
        for name, kinds in (("fused", ("bwd",)), ("two_pass", ("dq", "dkv"))):
            if name == "two_pass":
                pa.BWD_VMEM_BUDGET = 0
            plans = {kind: pa.kernel_plan(
                batch * heads, T, T, dim, q.dtype, True, window, kind=kind,
                group=group) for kind in kinds}
            row[name + "_plan"] = {
                kind: plan and {"heads": plan.heads, "chunk": plan.chunk_q,
                                "tile": plan.tile_q, "grid": plan.grid,
                                "vmem": plan.vmem_bytes}
                for kind, plan in plans.items()}
            if None in plans.values():
                continue
            fn = jax.jit(lambda *a: pa._pallas_bwd(
                *a, offs, True, False, window=window))
            try:
                grads[name] = fn(q, k, v, do, lse, delta)
                row[name + "_ms"] = round(
                    _clock(fn, iters, q, k, v, do, lse, delta), 3)
            except Exception as e:  # VMEM overflow etc.: report, go on
                row[name + "_error"] = str(e)[-300:]
    finally:
        pa.BWD_VMEM_BUDGET = budget
    if len(grads) == 2:
        row["speedup"] = round(row["two_pass_ms"] / row["fused_ms"], 3)
        row["maxdiff_fused_vs_two_pass"] = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32))))
            for a, b in zip(grads["fused"], grads["two_pass"]))
    return row


# The expert cells' grouped matmuls (benchmark/configs): rows of a window,
# the model's and the experts' widths, groups a layer, layers of a stage's
# stack, and the share of the window's rows the held groups cover on the
# first step (PERF.md section 5). "sums": the token-side sum of
# trinity-mini's window, blocks of 256 tokens as groups.
GMM_CELLS = {
    "olmoe": dict(m=65536, d=2048, f=1024, groups=64, layers=2, held=1.0),
    "zaya": dict(m=16384, d=2048, f=2048, groups=8, layers=10, held=0.5),
    "trinity": dict(m=32768, d=2048, f=1024, groups=16, layers=4,
                    held=0.59),
    "glm": dict(m=16384, d=2048, f=1536, groups=8, layers=4, held=0.55),
    "sums": dict(m=32768, d=256, f=2048, groups=64, layers=1, held=0.59),
}
# olmoe-t4096's first step at seed 1, its second layer: the rows each of
# the 64 experts got of 65,536 (the busiest 4.88 times the mean; my chip
# run, PR 41), the group sizes of "first_step".
OLMOE_FIRST_STEP_LOAD = (
    404, 2, 530, 4997, 1011, 94, 3248, 443, 2097, 103, 9, 496, 519, 2072,
    598, 2713, 37, 1206, 2437, 1718, 253, 131, 1260, 59, 862, 82, 2284,
    3040, 391, 116, 852, 161, 2026, 413, 1449, 3663, 2452, 313, 911, 3493,
    1221, 124, 319, 18, 683, 379, 50, 92, 48, 728, 206, 972, 803, 2365, 1,
    38, 597, 22, 3771, 28, 1238, 1247, 1405, 236)


def gmm_group_sizes(how, rows, groups, rng):
    """``groups`` sizes that sum to ``rows``: "exact" equal (to the row,
    the remainder on the last), "near" equal within 6 %, "skewed" as a
    seeded router's (log-normal shares, the busiest group about six times
    the mean at 64), "one" a single group of every row; "first_step"
    ``OLMOE_FIRST_STEP_LOAD`` where that is the shape, else None."""
    import numpy as np

    if how == "first_step":
        fits = (groups, rows) == (len(OLMOE_FIRST_STEP_LOAD),
                                  sum(OLMOE_FIRST_STEP_LOAD))
        return np.asarray(OLMOE_FIRST_STEP_LOAD, np.int32) if fits else None
    if how == "one":
        share = np.eye(1, groups)[0]
    elif how == "exact":
        share = np.ones(groups)
    elif how == "near":
        share = 1 + 0.06 * rng.uniform(-1, 1, groups)
    else:
        share = np.exp(0.9 * rng.randn(groups))
    sizes = np.floor(share / share.sum() * rows).astype(np.int32)
    sizes[np.argmax(sizes)] += rows - sizes.sum()
    return sizes


def bench_gmm(name, iters, hows=("exact", "near", "skewed", "one"),
              strips=(None,)):
    """Rows of JSON: one per (product, group sizes) of cell ``name``'s
    shape, ``megablox`` beside the new kernel (at each of ``strips``:
    None the plan's own, another a plan with that strip, the tile itself
    being "the contraction whole, every visit a whole tile")."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        gmm as megablox_gmm, tgmm as megablox_tgmm)

    from horovod_tpu.ops import grouped_matmul as gm

    cell = GMM_CELLS[name]
    m, d, f, groups, layers = (cell[key] for key in (
        "m", "d", "f", "groups", "layers"))
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    held = int(cell["held"] * m)
    # (kind, k, n): gate/up, down, the row gradient of each, tgmm of each.
    products = [("gmm", d, f), ("gmm", f, d), ("gmm_t", d, f),
                ("gmm_t", f, d), ("tgmm", d, f), ("tgmm", f, d)]
    if name == "sums":
        products = [("tgmm", d, f)]
    out = []
    for kind, k, n in products:
        lhs = jnp.asarray(rng.randn(m, k), bf16)
        if kind == "tgmm":
            rhs, stacked = jnp.asarray(rng.randn(m, n), bf16), groups
        else:
            # gmm_t contracts the matrices' second dimension.
            shape = (n, k) if kind == "gmm_t" else (k, n)
            stacked = layers * groups
            rhs = jnp.asarray(rng.randn(stacked, *shape) * k ** -0.5, bf16)
        tile = tuple(int(np.gcd(a, b)) for a, b in zip(
            (m, k, n), (512, 1024, 1024)))
        if kind == "tgmm":
            old = jax.jit(lambda a, b, s: megablox_tgmm(
                a.swapaxes(0, 1), b, s, bf16, tile, None, groups))
        else:
            old = jax.jit(lambda a, b, s, t=kind == "gmm_t": megablox_gmm(
                a, b, s, bf16, tile, None, None, t))
        plan = gm.kernel_plan(m, k, n, stacked, bf16, kind)
        for how in hows:
            sizes = gmm_group_sizes(how, held, groups, rng)
            if sizes is None:
                continue
            # The last layer's groups of the stack have the rows.
            padded = np.zeros(stacked, np.int32)
            padded[stacked - groups:] = sizes
            s = jnp.asarray(padded)
            row = {"cell": name, "kind": kind, "m": m, "k": k, "n": n,
                   "groups": groups, "stack": stacked, "sizes": how,
                   "rows_in_groups": int(sizes.sum()),
                   "max_over_mean": round(float(sizes.max() / sizes.mean()),
                                          2),
                   "megablox_tile": tile}
            try:
                want = old(lhs, rhs, s)
                row["megablox_ms"] = round(_clock(old, iters, lhs, rhs, s), 4)
            except Exception as e:  # VMEM overflow etc.: report, go on
                want, row["megablox_error"] = None, repr(e)[-300:]
            for strip in strips:
                p = plan if strip is None else plan._replace(strip=strip)
                tag = "new" if strip is None else f"new_strip{strip}"
                done, of = gm.visited_work(padded, m, p)
                row[tag + "_plan"] = dict(p._asdict())
                row[tag + "_strips"] = [done, of]
                if kind == "tgmm":
                    new = jax.jit(lambda a, b, s, p=p: gm.tgmm(
                        a, b, s, plan=p))
                else:
                    new = jax.jit(lambda a, b, s, p=p, t=kind == "gmm_t":
                                  gm.gmm(a, b, s, transpose_rhs=t, plan=p))
                try:
                    got = new(lhs, rhs, s)
                    row[tag + "_ms"] = round(
                        _clock(new, iters, lhs, rhs, s), 4)
                except Exception as e:
                    row[tag + "_error"] = repr(e)[-300:]
                    continue
                if want is not None:
                    cut = slice(None) if kind == "tgmm" else slice(
                        0, int(sizes.sum()))
                    row[tag + "_maxdiff"] = float(jnp.max(jnp.abs(
                        got[cut].astype(jnp.float32)
                        - want[cut].astype(jnp.float32))))
                    if strip is None and "megablox_ms" in row:
                        row["speedup"] = round(
                            row["megablox_ms"] / row[tag + "_ms"], 3)
            out.append(row)
            print(json.dumps(row))
            sys.stdout.flush()
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seq-lens", default="2048,4096")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--kind", choices=["all", "bwd", "gmm"], default="all",
                   help="bwd: the fused backward beside the two passes, "
                        "kernels alone; gmm: the grouped matmuls alone")
    p.add_argument("--gmm-cells", default=",".join(GMM_CELLS),
                   help="--kind gmm: which of " + ", ".join(GMM_CELLS))
    p.add_argument("--gmm-sizes", default="exact,near,skewed,one,first_step",
                   help="--kind gmm: group sizes (gmm_group_sizes; "
                        "first_step: olmoe's own, where written in)")
    p.add_argument("--gmm-strips", default="",
                   help="--kind gmm: also time plans with these strips "
                        "(the tile itself: every visit a whole tile)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="K/V heads (--kind bwd; default: --heads)")
    p.add_argument("--bwd-budgets", default=None,
                   help="--kind bwd: MiB of VMEM the fused plan may "
                        "count, one row each (default: the module's)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window width: measures the whole-tile "
                        "culling speedup vs the XLA masked path")
    p.add_argument("--sweep-blocks", action="store_true",
                   help="sweep kernel_plan's sub-tile cap per seq len")
    args = p.parse_args(argv)

    from benchmark.harness import claim_devices
    from tools.compile_cache import enable_compile_cache

    print(f"bench: compile cache at {enable_compile_cache()}",
          file=sys.stderr)
    _, device, _ = claim_devices(1)  # no TPU: exit 3, nothing timed
    print(json.dumps(device))
    if args.kind == "gmm":
        strips = (None,) + tuple(
            int(t) for t in args.gmm_strips.split(",") if t)
        for name in args.gmm_cells.split(","):
            bench_gmm(name, args.iters, tuple(args.gmm_sizes.split(",")),
                      strips)
        return 0
    for T in [int(t) for t in args.seq_lens.split(",")]:
        if args.kind == "bwd":
            import horovod_tpu.ops.pallas_attention as pa

            budget = pa.BWD_VMEM_BUDGET
            for mib in (args.bwd_budgets or str(budget >> 20)).split(","):
                pa.BWD_VMEM_BUDGET = int(mib) << 20
                print(json.dumps(bench_bwd(
                    T, args.iters, args.batch, args.heads,
                    args.kv_heads or args.heads, args.dim, args.window)))
                sys.stdout.flush()
            pa.BWD_VMEM_BUDGET = budget
        elif args.sweep_blocks:
            sweep_blocks(T, args.iters, args.batch, args.heads, args.dim)
        else:
            rows, _ = bench_one(T, args.iters, args.batch, args.heads,
                                args.dim, window=args.window)
            for row in rows:
                print(json.dumps(row))
                sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
