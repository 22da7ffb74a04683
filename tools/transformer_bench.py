#!/usr/bin/env python
"""Transformer training benchmark: tokens/sec and MFU for a GPT-2-small
class decoder on the sharded transformer (models/transformer.py).

Widens the headline evidence beyond the ResNet protocol (bench.py): the
same mesh machinery drives a causal LM step — flash attention, Megatron
tp sharding, sp context parallelism all exercised by flags. One JSON
line per run, same discipline as bench.py: it needs the chip (no
accelerator is a non-zero exit, never a CPU number) and every result
names the device jax reported in the process that ran the step.

    python tools/transformer_bench.py                  # GPT-2-small-ish
    python tools/transformer_bench.py --sp 4 --seq-len 8192   # long-ctx

MFU convention: model FLOPs per token = 6*N (N = MATMUL parameter
count — embedding table and learned positions excluded, untied output
head included; the standard fwd+bwd estimate with FMA counted as 2)
plus the attention term 12*L*T*d_attn (QK^T and PV, fwd+bwd, causality
NOT discounted — the kernel does the full matmul shape unless the
Pallas path skips masked tiles). Peak table matches bench.py.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# One peak-FLOPs table and one accelerator gate for the whole repo:
# bench.py owns them (repo root is already on sys.path above).
from bench import (  # noqa: E402
    _peak_flops, device_identity, require_accelerator)
from tools.compile_cache import enable_compile_cache  # noqa: E402


def _build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--n-heads", type=int, default=12)
    p.add_argument("--n-layers", type=int, default=12)
    p.add_argument("--vocab", type=int, default=50304,
                   help="GPT-2 vocab rounded up to a multiple of 128 "
                        "(lane-aligned for the MXU)")
    p.add_argument("--seq-len", type=int, default=1024,
                   help="GLOBAL sequence length")
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch (default: 8 per dp shard)")
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--strategy", default="ring",
                   choices=["ring", "ulysses", "auto"])
    p.add_argument("--n-kv-heads", type=int, default=None,
                   help="grouped-query attention: KV heads < --n-heads")
    p.add_argument("--rope", action="store_true",
                   help="rotary positions instead of the learned table")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window attention width")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1 over the dp axis: moments partitioned on "
                        "top of the params' sharding (pure sharding "
                        "annotations; measures the memory/perf trade)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize decoder layers (activation HBM "
                        "for FLOPs; measure the cost of the long-context "
                        "memory knob)")
    p.add_argument("--num-warmup", type=int, default=3)
    p.add_argument("--num-iters", type=int, default=20)
    return p


def run(args):
    """Run the configured decoder in this process; returns the result
    dict (``main`` prints it). Tests call this at a toy size on the CPU
    backend on purpose."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models.transformer import (
        TransformerConfig, init_params, make_train_step, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh
    from horovod_tpu.training import init_opt_state

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_parallel_mesh(jax.devices(), sp=args.sp, tp=args.tp,
                               pp=1)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if args.batch_size is None:
        args.batch_size = 8 * sizes["dp"]
    ident = device_identity()
    print(f"bench: mesh {sizes} on {ident}; "
          f"B={args.batch_size} T={args.seq_len}", file=sys.stderr)

    cfg = TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        d_head=args.d_model // args.n_heads, d_ff=4 * args.d_model,
        n_layers=args.n_layers, max_seq=args.seq_len, dtype=jnp.bfloat16,
        sp_strategy=args.strategy, remat=args.remat,
        n_kv_heads=args.n_kv_heads, rope=args.rope,
        attention_window=args.window)
    params = init_params(cfg, jax.random.PRNGKey(0), n_stages=1)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    # The 6N estimate counts matmul params only: the embedding table and
    # learned positions are gathers/adds, not matmuls (Kaplan
    # convention). The untied output head IS a matmul and stays in.
    n_matmul_params = n_params - sum(
        int(np.prod(params[k].shape)) for k in ("embed", "pos")
        if k in params)  # no "pos" table under RoPE

    sharded = shard_params(params, cfg, mesh)
    del params
    optimizer = optax.adamw(3e-4)
    opt_state = init_opt_state(optimizer, sharded, mesh,
                               zero_axis="dp" if args.zero else None)
    opt_shardings = (jax.tree_util.tree_map(lambda x: x.sharding, opt_state)
                     if args.zero else None)
    step = make_train_step(cfg, optimizer, mesh, n_microbatches=1,
                           opt_shardings=opt_shardings)

    rng = np.random.RandomState(0)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(
        rng.randint(0, cfg.vocab, (args.batch_size, args.seq_len)
                    ).astype(np.int32), data_sharding)
    labels = jnp.roll(tokens, -1, axis=1)

    for _ in range(max(1, args.num_warmup)):
        sharded, opt_state, loss = step(sharded, opt_state, tokens, labels)
    loss.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        sharded, opt_state, loss = step(sharded, opt_state, tokens, labels)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    n_chips = mesh.devices.size
    tokens_per_step = args.batch_size * args.seq_len
    tok_per_s = tokens_per_step * args.num_iters / dt
    # 6N matmul estimate + attention QK^T/PV term (fwd 2*2*T*d_attn per
    # token per layer, x3 for fwd+bwd). With a sliding window the Pallas
    # kernels cull out-of-window tiles, so the achievable attention span
    # per query is min(seq_len, window) — counting the full T here would
    # overstate MFU for SWA runs. (Causal masking still halves the real
    # work on average; that known overstatement is documented in
    # docs/benchmarks.md and applies equally with and without a window.)
    d_attn = args.n_heads * (args.d_model // args.n_heads)
    attn_span = (min(args.seq_len, args.window) if args.window
                 else args.seq_len)
    flops_per_token = (6 * n_matmul_params +
                       12 * args.n_layers * attn_span * d_attn)
    model_flops_per_s = tok_per_s * flops_per_token

    return {
        "metric": "transformer_tokens_per_sec_per_chip",
        "value": round(tok_per_s / n_chips, 1),
        "unit": "tokens/sec/chip",
        **ident,
        "n_params": n_params,
        "n_matmul_params": n_matmul_params,
        "d_model": args.d_model,
        "n_layers": args.n_layers,
        "seq_len": args.seq_len,
        "global_batch": args.batch_size,
        "mesh": sizes,
        "sp_strategy": args.strategy,
        "window": args.window,
        "zero": bool(args.zero),
        "loss": round(float(np.asarray(loss)), 4),
        "step_ms": round(1e3 * dt / args.num_iters, 2),
        "mfu": round(model_flops_per_s
                     / (n_chips * _peak_flops(ident["device_kind"])), 4),
    }


def main(argv=None):
    args = _build_parser().parse_args(argv)
    print(f"bench: compile cache at {enable_compile_cache()}",
          file=sys.stderr)
    require_accelerator()
    print(json.dumps(run(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
