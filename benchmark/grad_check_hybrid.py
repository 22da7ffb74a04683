#!/usr/bin/env python3
"""The program's gradient against ``reference_hybrid``'s at published
widths, on the chip, outside any timed window:

    python3 -m benchmark.grad_check_hybrid --seed <n>
        [--config granite-4.0-h-micro] [--seq-len 1024]

One sequence of ``--seq-len`` tokens from the seed (1,024: four chunks of
the scan, so the carried state is in it), float32 weights from the
program's ``init_params``. (1) The program's loss and gradient
(``transformer.make_loss_fn`` under ``jax.grad``, float32, matmuls and
kernels at ``highest`` precision) against the plain float32 reference's,
whose state-space layers are the recurrence over time: for every
parameter leaf the largest difference over the reference's largest entry,
held to ``--tol``, 5e-4. Why not the 1e-5 of ``grad_check_moe``: on the
chip it is the *reference* that is that far from the exact answer. Its
recurrence multiplies 1,024 decays ``exp(dt_t A)`` token by token, and
the chip's float32 ``exp`` has a mean relative error of -8e-7 (largest
5e-6) that does not average out over a product of a thousand; the
program takes one ``exp`` of a difference of float32 sums a pair. Against
a float64 recurrence of the same inputs (one layer's shapes, T 1,024; my
chip run, PR 30, PERF.md section 6) the chunked form is within 2e-7 to
6.4e-6 on the value and every gradient and the float32 recurrence within
8e-7 to 4.1e-4 (by ``dt``), 8.8e-5 by ``A``. Through the ten layers that
reads 2e-5 to 9e-5 on every leaf and 1.8e-4 on ``m_A_log``; on the CPU,
whose ``exp`` is good to the last bit, the same check at the same widths
reads 1.7e-6 to 4.8e-6 on every leaf but ``m_A_log`` and ``m_dt_bias``,
1.2e-5 and 1.3e-5 (PERF.md has both tables). A float32 part of the
program run in bf16 shows as 2e-2, the bf16 rows below.
(2) The same weights
rounded to bf16 through the bf16 program, as the benchmark runs it: its
distance from the float32 reference's gradient, reported, not held to a
tolerance. The two runs are made one after the other, each gradient
copied to the host, because float32 copies of both do not fit the chip at
once. Exit code 0 if (1) holds on every leaf."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def main(argv=None):
    from horovod_tpu.models.transformer import (
        init_params, make_loss_fn, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_hybrid
    from benchmark.runners import decoder_hybrid

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--config", default="granite-4.0-h-micro")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--tol", type=float, default=5e-4)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    devices = jax.devices()[:1]
    print(f"[grad] {args.config}, one sequence of {args.seq_len} tokens, "
          f"seed {args.seed}, on {devices[0].device_kind}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))
    k_params, k_tokens = jax.random.split(jax.random.PRNGKey(args.seed))
    tokens = jax.device_put(jax.random.randint(
        k_tokens, (1, args.seq_len), 0, config["vocab_size"], jnp.int32),
        data)
    labels = jnp.roll(tokens, -1, axis=1)

    def program(dtype, params):
        """(loss, gradient on the host) of the program in ``dtype`` on
        ``params`` cast to it."""
        job_cfg = decoder_hybrid.transformer_config(
            dict(config, dtype=dtype))
        typed = shard_params(jax.tree_util.tree_map(
            lambda a, like: a.astype(like.dtype), params,
            jax.eval_shape(lambda k: init_params(job_cfg, k, 1), k_params)),
            job_cfg, mesh)
        loss_fn = make_loss_fn(job_cfg, mesh, n_microbatches=1)
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(
                typed, tokens, labels)
        out = (float(loss), jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads))
        del typed, grads
        return out

    cfg = decoder_hybrid.transformer_config(dict(config, dtype="float32"))
    params = jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params)
    got_loss, got = program("float32", params)
    print(f"[grad] program, float32 at highest: loss {got_loss:.7f}",
          flush=True)
    model = decoder_hybrid.reference_model(config)
    ref_loss, ref = jax.jit(
        lambda p, t, l: reference_hybrid.decoder_hybrid_loss_and_grad(
            p, t, l, model))(params, tokens, labels)
    ref = jax.tree_util.tree_map(lambda g: np.asarray(g, np.float32), ref)
    print(f"[grad] reference: loss {float(ref_loss):.7f} (relative "
          f"difference "
          f"{abs(got_loss - float(ref_loss)) / float(ref_loss):.2e})",
          flush=True)

    def distances(a, b):
        return (float(np.abs(a - b).max() / np.abs(b).max()),
                float(np.linalg.norm(a - b) / np.linalg.norm(b)))

    worst = 0.0
    for name in sorted(ref):
        by_max, by_l2 = distances(got[name], ref[name])
        worst = max(worst, by_max)
        print(f"[grad]   float32 {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    ok = worst <= args.tol
    print(f"[grad] float32 program against the reference: worst leaf "
          f"{worst:.3e}, tolerance {args.tol:g}: "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    del got

    bf_loss, bf = program("bfloat16", params)
    print(f"[grad] program, bf16 as benchmarked: loss {bf_loss:.7f} "
          f"(relative difference from the float32 reference "
          f"{abs(bf_loss - float(ref_loss)) / float(ref_loss):.2e})",
          flush=True)
    for name in sorted(ref):
        by_max, by_l2 = distances(bf[name], ref[name])
        print(f"[grad]   bf16    {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    print(json.dumps({"ok": ok, "worst_float32_leaf": worst,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
