#!/usr/bin/env python3
"""The program's gradient against ``reference_ling``'s at published
widths, on the chip, outside any timed window:

    python3 -m benchmark.grad_check_ling --seed <n>
        [--config ling-3.0-flash] [--seq-len 1024]

Three layers of the cut at published widths, the published layers 3 to 5
(KDA with the dense MLP in the place of the cut's leading layer, KDA with
experts, MLA with experts), and the module after them: float32 copies of
the cut's eight layers, their gradients and the recurrence's states for
its backward pass do not fit the chip at once (the whole cut was tried:
6.30 G to reserve, 4.80 free). One sequence of ``--seq-len`` tokens from
the seed, float32 weights from
the program's ``init_params`` with both balancing biases drawn at 0.01 so
that they move picks and every KDA head's ``A`` drawn at 0.1 so that it is
no constant. The loss is the main cross-entropy plus 0.3 times the
multi-token-prediction module's. (1) The program's loss and gradient
(``transformer.make_loss_fn`` under ``jax.grad``, float32, matmuls and
kernels at ``highest`` precision: the scan in chunks through its Pallas
kernels, the latent mixers through the flash kernels with the values
padded; the held experts' matmuls as XLA's ``lax.ragged_dot``, because
the grouped-matmul kernels have no tiles for float32 operands at d 2,560
under their 16 MiB of VMEM, and the sums over a token's rows as the
masked lookup beside them) against the plain float32 reference's, whose KDA is the recurrence
a token at a time: for every trained leaf the largest difference over the
reference's largest entry, held to ``--tol`` where the two route every
assignment alike, and the biases' own gradients, which have to be zero.
The tolerance is 5e-5, five times the other decoders': the chunked scan's
decays are ``exp`` of sums of up to 64 log decays where the recurrence
multiplies 64 decays, and the chip's float32 ``exp`` is 1e-5 coarse in
relative terms (``ops/pallas_attention.row_lse``'s note). A token whose
eighth and ninth expert change places between the two gets or loses a
whole held expert; where assignments are routed differently (their count
is printed) every leaf's relative L2 is held to ``--moved-l2`` (5e-2)
times the root of their number instead: run another seed for the tight
criterion. (2) The same weights rounded to bf16 through the bf16 program,
as the benchmark runs it: its distance from the float32 reference's
gradient, reported, not held to a tolerance. The two runs are made one
after the other, each gradient copied to the host. Exit code 0 if (1)
holds on every leaf."""
import argparse
import contextlib
import json
import os
import sys
import types
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


BIASES = ("expert_bias", "mtp_expert_bias")


def main(argv=None):
    from horovod_tpu.models.transformer import (
        init_params, make_loss_fn, shard_params)
    from horovod_tpu.parallel import moe
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_ling
    from benchmark.runners import decoder_ling

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--config", default="ling-3.0-flash")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--tol", type=float, default=5e-5)
    parser.add_argument("--moved-l2", type=float, default=5e-2)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=3, layers_run_published=[3, 5],
                  layer_types=["kda", "kda", "latent_attention"])
    devices = jax.devices()[:1]
    print(f"[grad] {args.config}, published layers 3 to 5, one sequence of {args.seq_len} tokens, "
          f"seed {args.seed}, on {devices[0].device_kind}", flush=True)
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
        job_cfg = decoder_ling.transformer_config(dict(config,
                                                        dtype=dtype))
        typed = shard_params(jax.tree_util.tree_map(
            lambda a, like: a.astype(like.dtype), params,
            jax.eval_shape(lambda k: init_params(job_cfg, k, 1), k_params)),
            job_cfg, mesh)
        loss_fn = make_loss_fn(job_cfg, mesh, n_microbatches=1,
                               with_readings=True)
        # In float32 the expert layer off its kernels (the docstring).
        experts = mock.patch.object(
            moe, "_pallas_attention", types.SimpleNamespace(
                _resolve_dispatch=lambda use_pallas: (False, False))
        ) if dtype == "float32" else contextlib.nullcontext()
        with experts, jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            (loss, readings), grads = jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True))(typed, tokens, labels)
        out = (float(loss), jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads),
            np.asarray(readings["load"])[job_cfg.num_dense_layers:])
        del typed, grads
        return out

    cfg = decoder_ling.transformer_config(dict(config, dtype="float32"))
    model = decoder_ling.reference_model(config)
    params = jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params)
    for salt, name in enumerate(BIASES, 1):
        params[name] = 0.01 * jax.random.normal(
            jax.random.fold_in(k_params, salt), params[name].shape)
    params["k_A"] = 0.1 * jax.random.normal(
        jax.random.fold_in(k_params, 3), params["k_A"].shape)
    got_loss, got, got_load = program("float32", params)
    bias_grad = max(float(np.abs(got.pop(name)).max()) for name in BIASES)
    print(f"[grad] program, float32 at highest: loss {got_loss:.7f}; the "
          f"bias's own gradient at most {bias_grad:g}", flush=True)
    # A jitted layer at a time (``reference_ling.forward``): no outer jit.
    ref_loss, ref = reference_ling.loss_and_grad(params, tokens, labels,
                                                 model)
    ref_load = reference_ling.forward(params, tokens, labels, model)[2]
    ref = jax.tree_util.tree_map(lambda g: np.asarray(g, np.float32), ref)
    moved = int(np.abs(np.asarray(ref_load) - got_load).sum()) // 2
    print(f"[grad] reference: loss {float(ref_loss):.7f} (relative "
          f"difference {abs(got_loss - float(ref_loss)) / float(ref_loss):.2e}"
          f"); assignments the two route differently: {moved} of "
          f"{int(got_load.sum())}", flush=True)

    def distances(a, b):
        return (float(np.abs(a - b).max() / np.abs(b).max()),
                float(np.linalg.norm(a - b) / np.linalg.norm(b)))

    worst = worst_l2 = 0.0
    for name in sorted(ref):
        by_max, by_l2 = distances(got[name], ref[name])
        worst, worst_l2 = max(worst, by_max), max(worst_l2, by_l2)
        print(f"[grad]   float32 {name:15s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    if moved:
        held_to = args.moved_l2 * moved ** 0.5
        ok = worst_l2 <= held_to and bias_grad == 0.0
        print(f"[grad] float32 program against the reference, {moved} "
              f"assignment(s) routed differently: worst leaf {worst_l2:.3e} "
              f"in relative L2, held to {held_to:g}: "
              f"{'ok' if ok else 'FAILED'}", flush=True)
    else:
        ok = worst <= args.tol and bias_grad == 0.0
        print(f"[grad] float32 program against the reference: worst leaf "
              f"{worst:.3e}, tolerance {args.tol:g}: "
              f"{'ok' if ok else 'FAILED'}", flush=True)
    del got

    bf_loss, bf, bf_load = program("bfloat16", params)
    for name in BIASES:
        bf.pop(name)
    bf_moved = int(np.abs(np.asarray(ref_load) - bf_load).sum()) // 2
    print(f"[grad] program, bf16 as benchmarked: loss {bf_loss:.7f}; "
          f"assignments routed differently from the float32 reference: "
          f"{bf_moved}", flush=True)
    for name in sorted(ref):
        by_max, by_l2 = distances(bf[name], ref[name])
        print(f"[grad]   bf16    {name:15s} largest difference / largest "
              f"entry {by_max:.3e}   relative L2 {by_l2:.3e}", flush=True)
    print(json.dumps({"ok": ok, "worst_float32_leaf": worst,
                      "worst_float32_leaf_l2": worst_l2,
                      "routed_differently": moved,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
