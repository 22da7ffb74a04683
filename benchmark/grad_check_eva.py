#!/usr/bin/env python3
"""The program's gradient against ``reference_eva``'s at published widths,
on the chip, outside any timed window:

    python3 -m benchmark.grad_check_eva --seed <n>
        [--config evabyte] [--seq-len 8192] [--layers 2]

One sequence of ``--seq-len`` bytes from the seed (four windows of 2,048:
every query past the first window sees summaries, the last window those
of three) through the first ``--layers`` layers of the configuration
(float32 copies of all four, their gradients and the reference's do not
fit the chip at once), float32 weights from the program's ``init_params``
with the norms' weights, which start at zero under the unit offset, drawn
at 0.1, so that no gradient is uninformative by symmetry. (1) The
program's loss and gradient (``transformer.make_loss_fn`` under
``jax.grad``, float32, matmuls and kernels at ``highest`` precision, the
MLP by blocks of tokens, the kernels under both mask rules) against the
plain float32 reference's: for every leaf (the pooling vectors ``e_mu``,
``e_phi`` and the eight heads' matrix among them) the largest difference
over the reference's largest entry, held to ``--tol`` (1e-5). (2) The
same weights rounded to bf16 through the bf16 program, as the benchmark
runs it: its distance from the float32 reference's gradient, reported,
not held to a tolerance. Exit code 0 if (1) holds on every leaf."""
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

NORMS = ("ln1", "ln2", "final_ln")


def main(argv=None):
    from horovod_tpu.models.transformer import (
        init_params, make_loss_fn, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_eva
    from benchmark.runners import decoder_eva

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--config", default="evabyte")
    parser.add_argument("--seq-len", type=int, default=8192)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--tol", type=float, default=1e-5)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = dict(json.load(f), num_hidden_layers=args.layers)
    devices = jax.devices()[:1]
    print(f"[grad] {args.config}, {args.layers} layers, one sequence of "
          f"{args.seq_len} bytes, seed {args.seed}, on "
          f"{devices[0].device_kind}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))
    k_params, k_tokens = jax.random.split(jax.random.PRNGKey(args.seed))
    tokens, labels = (jax.device_put(x, data) for x in
                      decoder_eva.make_batch(k_tokens, (1, args.seq_len),
                                             config["vocab_size"]))

    def program(dtype, params):
        """(loss, gradient on the host) of the program in ``dtype`` on
        ``params`` cast to it."""
        job_cfg = decoder_eva.transformer_config(dict(config, dtype=dtype))
        typed = shard_params(jax.tree_util.tree_map(
            lambda a, like: a.astype(like.dtype), params,
            jax.eval_shape(lambda k: init_params(job_cfg, k, 1), k_params)),
            job_cfg, mesh)
        loss_fn = make_loss_fn(job_cfg, mesh, n_microbatches=1)
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(
                typed, tokens, labels)
        out = float(loss), jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads)
        del typed, grads
        return out

    cfg = decoder_eva.transformer_config(dict(config, dtype="float32"))
    model = decoder_eva.reference_model(config)
    params = jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params)
    for salt, name in enumerate(NORMS, 1):
        params[name] = 0.1 * jax.random.normal(
            jax.random.fold_in(k_params, salt), params[name].shape)
    got_loss, got = program("float32", params)
    print(f"[grad] program, float32 at highest: loss {got_loss:.7f}",
          flush=True)
    ref_loss, ref = reference_eva.loss_and_grad(params, tokens, labels,
                                                model)
    ref = jax.tree_util.tree_map(lambda g: np.asarray(g, np.float32), ref)
    print(f"[grad] reference: loss {float(ref_loss):.7f} (relative "
          f"difference {abs(got_loss - float(ref_loss)) / float(ref_loss):.2e}"
          f")", flush=True)

    def distances(a, b):
        return (float(np.abs(a - b).max() / np.abs(b).max()),
                float(np.linalg.norm(a - b) / np.linalg.norm(b)))

    worst = worst_l2 = 0.0
    for name in sorted(ref):
        by_max, by_l2 = distances(got[name], ref[name])
        worst, worst_l2 = max(worst, by_max), max(worst_l2, by_l2)
        print(f"[grad]   float32 {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    ok = worst <= args.tol
    print(f"[grad] float32 program against the reference: worst leaf "
          f"{worst:.3e}, tolerance {args.tol:g}: "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    del got

    bf_loss, bf = program("bfloat16", params)
    print(f"[grad] program, bf16 as benchmarked: loss {bf_loss:.7f}",
          flush=True)
    for name in sorted(ref):
        by_max, by_l2 = distances(bf[name], ref[name])
        print(f"[grad]   bf16    {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    print(json.dumps({"ok": ok, "worst_float32_leaf": worst,
                      "worst_float32_leaf_l2": worst_l2,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
