#!/usr/bin/env python3
"""The two readings that ``decoder_hybrid.NLL_RMS_TOL`` stands between,
at the cell's own sizes, on the chip, outside any timed window:

    python3 -m benchmark.limit_check_hybrid --seed <n> [<n> ...]
        [--config granite-4.0-h-micro] [--seq-len 8192]

For each seed the runner's own weights and batch (bf16 parameters from
the program's ``init_params``, one sequence of ``--seq-len`` tokens), the
plain float32 reference's cross-entropy of every token (the state-space
layers as a recurrence), and against it the program's forward pass
(``decoder_hybrid.program_token_nll``) as the root of the mean squared
difference over the tokens (the largest difference beside it):

* as the configuration states it: bf16 parameters, activations and
  matmul operands; float32 ``dt``, decays and their sums, chunk states,
  norms, logits and loss;
* with one of those float32 parts at a time in bf16, everything else as
  stated and the cross-entropy itself float32. The program has no switch
  for any of this, so each is a patch of one name while the forward pass
  is traced (``PARTS`` below), undone after it.

Exit code 0 if every reading as stated is within the limit and every
part in ``SEEN`` reads over it. The parts outside ``SEEN`` are printed
too: bf16 there rounds no more coarsely than the bf16 activations that
the configuration states already do, and a comparison with a float32
reference cannot tell the two (PERF.md section 6, PR 30, has the
readings)."""
import argparse
import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32


def _einsums_in_bf16(*subscripts):
    """``jnp.einsum`` with bf16 operands and a bf16 result (widened
    again) for these subscripts, itself for every other."""
    einsum = jnp.einsum

    def patched(spec, *operands, **kwargs):
        if spec not in subscripts:
            return einsum(spec, *operands, **kwargs)
        return einsum(spec, *(a.astype(BF16) for a in operands),
                      preferred_element_type=BF16).astype(F32)

    return mock.patch.object(jnp, "einsum", patched)


def _decay_sums():
    cumsum = jnp.cumsum
    return mock.patch.object(jnp, "cumsum", lambda a, axis: cumsum(
        a.astype(BF16), axis=axis).astype(F32))


def _step_size():
    softplus = jax.nn.softplus
    return mock.patch.object(jax.nn, "softplus", lambda a: softplus(
        a.astype(BF16)).astype(F32))


def _gated_norm():
    from horovod_tpu.models import transformer

    def gated_rmsnorm(y, z, scale, eps):
        v = y.astype(BF16) * jax.nn.silu(z.astype(BF16))
        ms = jnp.mean(jnp.square(v), (2, 3), keepdims=True)
        return (v * lax.rsqrt(ms + eps) * scale.astype(BF16)).astype(y.dtype)

    return mock.patch.object(transformer, "_gated_rmsnorm", gated_rmsnorm)


def _block_norms():
    from horovod_tpu.models import transformer

    def rmsnorm(x, scale, eps):
        v = x.astype(BF16)
        ms = jnp.mean(jnp.square(v), -1, keepdims=True)
        return (v * lax.rsqrt(ms + eps) * scale.astype(BF16)).astype(x.dtype)

    return mock.patch.object(transformer, "_rmsnorm", rmsnorm)


# Name of the part -> the patch that runs it in bf16.
PARTS = {
    "as stated": contextlib.nullcontext,
    "decay sums (both cumulative sums of dt A)": _decay_sums,
    "dt (the softplus)": _step_size,
    "chunk states and their carry": lambda: _einsums_in_bf16(
        "bcjhp,bcjn->bchpn", "bhcz,bzhpn->bchpn"),
    "gated norm": _gated_norm,
    "block norms": _block_norms,
    "logits (the head's result)": lambda: _einsums_in_bf16("btd,vd->btv"),
}
# The parts whose bf16 the limit has to refuse.
SEEN = ("decay sums (both cumulative sums of dt A)",)


def main(argv=None):
    from horovod_tpu.models.transformer import init_params, shard_params
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_hybrid
    from benchmark.runners import decoder_hybrid

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--config", default="granite-4.0-h-micro")
    parser.add_argument("--seq-len", type=int, default=8192)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    cfg = decoder_hybrid.transformer_config(config)
    model = decoder_hybrid.reference_model(config)
    tol = decoder_hybrid.NLL_RMS_TOL
    devices = jax.devices()[:1]
    print(f"[limit] {args.config}, one sequence of {args.seq_len} tokens, "
          f"on {devices[0].device_kind}; limit {tol:g}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))
    reference = jax.jit(lambda p, t, l: reference_hybrid.token_nll(
        p, t, l, model))

    readings = {part: [] for part in PARTS}
    for seed in args.seed:
        # As the runner makes them.
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        params = shard_params(
            jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params),
            cfg, mesh)
        tokens = jax.device_put(jax.random.randint(
            k_tokens, (1, args.seq_len), 0, config["vocab_size"],
            jnp.int32), data)
        labels = jnp.roll(tokens, -1, axis=1)
        want = reference(params, tokens, labels)
        print(f"[limit] seed {seed}: reference loss "
              f"{float(jnp.mean(want)):.7f}", flush=True)
        for part, patch in PARTS.items():
            jax.clear_caches()  # no trace of another part's is met again
            with patch():
                got = decoder_hybrid.program_token_nll(cfg, mesh)(
                    params, tokens, labels)
            rms = decoder_hybrid.nll_rms(got, want)
            readings[part].append(rms)
            print(f"[limit]   {part:42s} rms {rms:.4e}   largest "
                  f"{float(jnp.max(jnp.abs(got - want))):.4e}   loss "
                  f"{float(jnp.mean(got)):.7f}", flush=True)
        del params

    stated = max(readings["as stated"])
    least = min(min(readings[part]) for part in SEEN)
    ok = stated <= tol < least
    print(f"[limit] as stated at most {stated:.4e}; the parts the limit "
          f"refuses at least {least:.4e}; limit {tol:g}: "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    print(json.dumps({"ok": ok, "as_stated_max": stated,
                      "refused_min": least, "limit": tol,
                      "readings": readings,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
