#!/usr/bin/env python3
"""The program's gradient against ``reference_zaya``'s at published widths,
on the chip, outside any timed window:

    python3 -m benchmark.grad_check_zaya --seed <n>
        [--config zaya1-8b] [--seq-len 4096] [--layers 3]

One sequence of ``--seq-len`` tokens from the seed through the first
``--layers`` layers of the configuration (float32 copies of all ten, their
gradients and the reference's do not fit the chip at once; the router's
state crosses two boundaries at three), float32 weights from the program's
``init_params`` with the balancing bias drawn at 0.01 so that it moves
picks, and every float32 leaf that starts at a constant (the norms'
weights, the residual scales, the q/k norm's temperature, the carried
state's weight) moved off it by 0.1, so that no gradient is zero or
uninformative by symmetry. (1) The program's loss and gradient
(``transformer.make_loss_fn`` under ``jax.grad``, float32, matmuls and
kernels at ``highest`` precision, the head by blocks) against the plain
float32 reference's: for every trained leaf (the convolution filters,
``c_beta``, ``r_gamma`` and the router's MLP among them) the largest
difference over the reference's largest entry, held to ``--tol`` (1e-5)
where the two route every token alike, and the bias's own gradient, which
has to be zero. A token that the two route differently gets or loses a
whole held expert: where there are such (their count is printed) every
leaf's relative L2 is held to ``--moved-l2`` (5e-2) times the root of
their number instead: run another seed for the tight criterion. (2) The
same weights rounded to bf16 through the bf16 program, as the benchmark
runs it: its distance from the float32 reference's gradient, reported, not
held to a tolerance. Exit code 0 if (1) holds on every leaf."""
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

BIAS = "expert_bias"
CONSTANTS = ("ln1", "ln2", "final_ln", "res1", "res2", "c_beta", "r_gamma",
             "r_norm")


def main(argv=None):
    from horovod_tpu.models.transformer import (
        init_params, make_loss_fn, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_zaya
    from benchmark.runners import decoder_zaya

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--config", default="zaya1-8b")
    parser.add_argument("--seq-len", type=int, default=4096)
    parser.add_argument("--layers", type=int, default=3)
    parser.add_argument("--tol", type=float, default=1e-5)
    parser.add_argument("--moved-l2", type=float, default=5e-2)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = dict(json.load(f), num_hidden_layers=args.layers)
    devices = jax.devices()[:1]
    print(f"[grad] {args.config}, {args.layers} layers, one sequence of "
          f"{args.seq_len} tokens, seed {args.seed}, on "
          f"{devices[0].device_kind}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))
    k_params, k_tokens = jax.random.split(jax.random.PRNGKey(args.seed))
    tokens = jax.device_put(jax.random.randint(
        k_tokens, (1, args.seq_len), 0, config["vocab_size"], jnp.int32),
        data)
    labels = jnp.roll(tokens, -1, axis=1)

    def program(dtype, params):
        """(loss, gradient on the host, tokens per expert) of the
        program in ``dtype`` on ``params`` cast to it."""
        job_cfg = decoder_zaya.transformer_config(dict(config, dtype=dtype))
        typed = shard_params(jax.tree_util.tree_map(
            lambda a, like: a.astype(like.dtype), params,
            jax.eval_shape(lambda k: init_params(job_cfg, k, 1), k_params)),
            job_cfg, mesh)
        loss_fn = make_loss_fn(job_cfg, mesh, n_microbatches=1,
                               with_readings=True)
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            (loss, readings), grads = jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True))(typed, tokens, labels)
        out = (float(loss), jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads),
            np.asarray(readings["load"]))
        del typed, grads
        return out

    cfg = decoder_zaya.transformer_config(dict(config, dtype="float32"))
    model = decoder_zaya.reference_model(config)
    params = jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params)
    for salt, name in enumerate((BIAS,) + CONSTANTS, 1):
        params[name] = params[name] + (0.01 if name == BIAS else 0.1) \
            * jax.random.normal(jax.random.fold_in(k_params, salt),
                                params[name].shape)
    got_loss, got, got_load = program("float32", params)
    bias_grad = float(np.abs(got.pop(BIAS)).max())
    print(f"[grad] program, float32 at highest: loss {got_loss:.7f}; the "
          f"bias's own gradient at most {bias_grad:g}", flush=True)
    ref_loss, ref = reference_zaya.loss_and_grad(params, tokens, labels,
                                                 model)
    ref_load = reference_zaya.forward(params, tokens, labels, model)[1]
    ref = jax.tree_util.tree_map(lambda g: np.asarray(g, np.float32), ref)
    moved = int(np.abs(np.asarray(ref_load) - got_load).sum()) // 2
    print(f"[grad] reference: loss {float(ref_loss):.7f} (relative "
          f"difference {abs(got_loss - float(ref_loss)) / float(ref_loss):.2e}"
          f"); tokens the two route differently: {moved} of "
          f"{int(got_load.sum())}", flush=True)

    def distances(a, b):
        return (float(np.abs(a - b).max() / np.abs(b).max()),
                float(np.linalg.norm(a - b) / np.linalg.norm(b)))

    worst = worst_l2 = 0.0
    for name in sorted(ref):
        by_max, by_l2 = distances(got[name], ref[name])
        worst, worst_l2 = max(worst, by_max), max(worst_l2, by_l2)
        print(f"[grad]   float32 {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    if moved:
        held_to = args.moved_l2 * moved ** 0.5
        ok = worst_l2 <= held_to and bias_grad == 0.0
        print(f"[grad] float32 program against the reference, {moved} "
              f"token(s) routed differently: worst leaf {worst_l2:.3e} in "
              f"relative L2, held to {held_to:g}: "
              f"{'ok' if ok else 'FAILED'}", flush=True)
    else:
        ok = worst <= args.tol and bias_grad == 0.0
        print(f"[grad] float32 program against the reference: worst leaf "
              f"{worst:.3e}, tolerance {args.tol:g}: "
              f"{'ok' if ok else 'FAILED'}", flush=True)
    del got

    bf_loss, bf, bf_load = program("bfloat16", params)
    bf.pop(BIAS)
    bf_moved = int(np.abs(np.asarray(ref_load) - bf_load).sum()) // 2
    print(f"[grad] program, bf16 as benchmarked: loss {bf_loss:.7f}; "
          f"tokens routed differently from the float32 reference: "
          f"{bf_moved}", flush=True)
    for name in sorted(ref):
        by_max, by_l2 = distances(bf[name], ref[name])
        print(f"[grad]   bf16    {name:10s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    print(json.dumps({"ok": ok, "worst_float32_leaf": worst,
                      "worst_float32_leaf_l2": worst_l2,
                      "routed_differently": moved,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
