#!/usr/bin/env python3
"""The readings that ``decoder_glm_lite``'s limits stand between, at the
cell's own sizes, on the chip, outside any timed window:

    python3 -m benchmark.limit_check_glm_lite --seed <n> [<n> ...]
        [--config glm-4.7-flash] [--seq-len 8192] [--batch 2]

For each seed the runner's own weights and batch (bf16 parameters from the
program's ``init_params``, ``--batch`` sequences of ``--seq-len`` tokens),
the plain float32 reference's two cross-entropies of every token and its
tokens per expert, and against them the program's loss function with its
readings (``make_loss_fn(with_readings=True)``: the forward pass the train
step differentiates):

* as the configuration states it;
* with one float32 part at a time in bf16 (the two low-rank norms of the
  latent mixers; the router's matmul and scores) and with every float32
  part at once (every norm, the router, both heads' logits: the
  configuration computed in the nearest precision below the one it
  states); everything else as stated, the cross-entropies float32.
  The program has no switch for either, so each is a patch of one name
  while the loss function is traced, undone after it. Every value of the
  part is rounded to bf16 where it is computed by ``lax.reduce_precision``
  (``limit_check_afmoe``'s helpers): a cast to bf16 and back is no
  rounding on the chip;
* with one piece of the mathematics at a time wrong: a query head rotated
  over all its channels, or over its first ``qk_rope_head_dim`` channels
  in place of its last; the rotated key given to the first head alone, not
  shared; the softmax scale ``qk_nope_head_dim``^-1/2; the
  multi-token-prediction module embedding ``t_i`` in place of
  ``t_{i+1}``, or predicting ``t_{i+1}`` in place of ``t_{i+2}``; the
  module's loss weight zero.

Six readings each: the loss's relative difference; of the main and of the
module's cross-entropies the root of the mean squared difference and the
median of the absolute difference; the share of the assignments routed to
another expert than the reference routes them. ``--part`` runs the named
parts alone (beside "as stated"). Exit code 0 if every reading as stated
is within the runner's six limits and every other part that was run, but
those of ``REPORTED``, is refused by at least one of them at every
seed."""
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark.limit_check_afmoe import (  # noqa: E402
    F32, _bf16, _every_part, _other, _patched, _router)


# Run and reported, not held to be refused: the two low-rank norms alone
# in bf16 read 9 to 16 % over their own seed's sound median, which the
# seeds themselves move by 9 % (PERF.md section 6, PR 36), so no limit
# with room for a sound run refuses them at every seed;
# tests/test_glm_lite.py holds their type in the traced step instead.
REPORTED = ("the two low-rank norms in bf16",)


def _low_rank_norms_in_bf16(cfg):
    """``transformer._rmsnorm`` in bf16 for the two latents (operands as
    wide as a rank), itself for every other."""
    from horovod_tpu.models import transformer

    rmsnorm = transformer._rmsnorm
    ranks = (cfg.q_lora_rank, cfg.kv_lora_rank)

    def norm(x, scale, eps):
        if x.shape[-1] not in ranks:
            return rmsnorm(x, scale, eps)
        v = _bf16(x.astype(F32))
        ms = _bf16(jnp.mean(_bf16(jnp.square(v)), -1, keepdims=True))
        normed = _bf16(v * _bf16(lax.rsqrt(ms + eps)))
        return _bf16(normed * _bf16(scale.astype(F32))).astype(x.dtype)

    return mock.patch.object(transformer, "_rmsnorm", norm)


def _query_rotated(where):
    """A query head's rotation (the call of ``transformer._rope`` that
    names its last channels) over ``where`` instead: "all" of a head's
    channels, or its "first" as many."""
    from horovod_tpu.models import transformer

    rope = transformer._rope

    def wrong(x, positions, theta, last=None):
        if last is None:
            return rope(x, positions, theta)
        if where == "all":
            return rope(x, positions, theta)
        return jnp.concatenate([rope(x[..., :last], positions, theta),
                                x[..., last:]], -1)

    return mock.patch.object(transformer, "_rope", wrong)


def _rotated_key_not_shared(cfg):
    """The one rotated key head given to the first query head alone: the
    others' rotated channels are zero."""
    broadcast_to = jnp.broadcast_to

    def wrong(x, shape):
        if not (len(shape) == 4 and shape[-1] == cfg.qk_rope_head_dim
                and x.ndim == 4 and x.shape[2] == 1):
            return broadcast_to(x, shape)
        return jnp.pad(x, [(0, 0), (0, 0), (0, shape[2] - 1), (0, 0)])

    return mock.patch.object(jnp, "broadcast_to", wrong)


def _softmax_scale_of_the_unrotated_width(cfg):
    """Scores over sqrt(qk_nope_head_dim): the kernels keep their
    d_head^-1/2, the rest goes on q."""
    from horovod_tpu.models import transformer

    attend = transformer.context_parallel_attention
    factor = (cfg.d_head / cfg.qk_nope_head_dim) ** 0.5

    def wrong(q, k, v, **kw):
        return attend((q.astype(F32) * factor).astype(q.dtype), k, v, **kw)

    return mock.patch.object(transformer, "context_parallel_attention",
                             wrong)


def _module(change):
    """The multi-token-prediction module on other tokens: ``change``
    (inputs, targets) -> (inputs, targets)."""
    from horovod_tpu.models import transformer

    module = transformer._mtp_module

    def wrong(cfg, layer_fn, params, hidden, inputs, targets):
        return module(cfg, layer_fn, params, hidden,
                      *change(inputs, targets))

    return mock.patch.object(transformer, "_mtp_module", wrong)


def parts(cfg):
    """Name of the part -> (the patch around the trace, the program's
    configuration from the stated one)."""
    return {
        "as stated": _patched(contextlib.nullcontext),
        "the two low-rank norms in bf16": _patched(
            lambda: _low_rank_norms_in_bf16(cfg)),
        "router (matmul and scores) in bf16": _patched(_router),
        # limit_check_afmoe's: every norm (here all are of rank-3
        # operands, the low-rank ones among them), the router and both
        # heads' logits at once.
        "every float32 part in bf16": _patched(_every_part),
        "a query head rotated over all its channels": _patched(
            lambda: _query_rotated("all")),
        "a query head rotated over its first channels": _patched(
            lambda: _query_rotated("first")),
        "the rotated key not shared": _patched(
            lambda: _rotated_key_not_shared(cfg)),
        "softmax scale of the unrotated width": _patched(
            lambda: _softmax_scale_of_the_unrotated_width(cfg)),
        # t_i is t_{i+1} rolled back by one.
        "the module embeds t_i": _patched(lambda: _module(
            lambda inputs, targets: (jnp.roll(inputs, 1, axis=1),
                                     targets))),
        "the module predicts t_{i+1}": _patched(lambda: _module(
            lambda inputs, targets: (inputs, inputs))),
        "the module's loss weight zero": _other(mtp_loss_weight=0.0),
    }


def main(argv=None):
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_glm_lite
    from benchmark.runners import decoder_glm_lite as runner

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--config", default="glm-4.7-flash")
    parser.add_argument("--seq-len", type=int, default=8192)
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--part", nargs="+")
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    cfg = runner.transformer_config(config)
    model = runner.reference_model(config)
    limits = dict(loss=runner.LOSS_RTOL, nll_rms=runner.NLL_RMS_TOL,
                  nll_median=runner.NLL_MEDIAN_TOL,
                  mtp_rms=runner.MTP_NLL_RMS_TOL,
                  mtp_median=runner.MTP_NLL_MEDIAN_TOL,
                  moved=runner.MOVED_SHARE_TOL)
    devices = jax.devices()[:1]
    print(f"[limit] {args.config}, {args.batch} x {args.seq_len} tokens, on "
          f"{devices[0].device_kind}; limits {limits}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))

    def inputs(seed):
        """As the runner makes them."""
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        params = transformer.shard_params(
            jax.jit(lambda k: transformer.init_params(cfg, k, n_stages=1))(
                k_params), cfg, mesh)
        tokens = jax.device_put(jax.random.randint(
            k_tokens, (args.batch, args.seq_len), 0, config["vocab_size"],
            jnp.int32), data)
        return params, tokens, jnp.roll(tokens, -1, axis=1)

    reference = jax.jit(lambda p, t, l: reference_glm_lite.step_readings(
        p, t, l, model))
    wants = {}
    for seed in args.seed:
        want = reference(*inputs(seed))
        wants[seed] = dict(want, load=np.asarray(want["load"]),
                           loss=float(want["loss"]))
        print(f"[limit] seed {seed}: reference loss "
              f"{wants[seed]['loss']:.7f}", flush=True)

    all_parts = {part: how for part, how in parts(cfg).items()
                 if part == "as stated" or not args.part
                 or part in args.part}
    readings = {part: [] for part in all_parts}
    for part, (patch, configured) in all_parts.items():
        jax.clear_caches()  # no trace of another part's is met again
        part_cfg = configured(cfg)
        program = jax.jit(transformer.make_loss_fn(
            part_cfg, mesh, n_microbatches=1, with_readings=True))
        for seed in args.seed:
            params, tokens, labels = inputs(seed)
            with patch():  # traced at the first seed, under the patch
                loss, got = program(params, tokens, labels)
            want = wants[seed]
            load = np.asarray(got["load"])[part_cfg.num_dense_layers:]
            reading = dict(
                loss=abs(float(loss) - want["loss"]) / want["loss"],
                nll_rms=runner.nll_rms(got["token_nll"], want["nll"]),
                nll_median=runner.nll_median(got["token_nll"],
                                             want["nll"]),
                mtp_rms=runner.nll_rms(got["mtp_token_nll"],
                                       want["mtp_nll"]),
                mtp_median=runner.nll_median(got["mtp_token_nll"],
                                             want["mtp_nll"]),
                moved=float(np.abs(load - want["load"]).sum() // 2
                            / want["load"].sum()))
            readings[part].append(reading)
            refused = [k for k in limits if reading[k] > limits[k]]
            print(f"[limit] {part:45s} seed {seed}: loss "
                  f"{reading['loss']:.3e}   main rms "
                  f"{reading['nll_rms']:.4e} median "
                  f"{reading['nll_median']:.4e}   module rms "
                  f"{reading['mtp_rms']:.4e} median "
                  f"{reading['mtp_median']:.4e}   routed elsewhere "
                  f"{reading['moved']:.5f}   refused by {refused}",
                  flush=True)
            del params

    def refused(reading):
        return any(reading[k] > limits[k] for k in limits)

    sound = not any(map(refused, readings["as stated"]))
    seen = {part: all(map(refused, readings[part]))
            for part in all_parts if part != "as stated"}
    ok = sound and all(seen[part] for part in seen
                       if part not in REPORTED)
    print(f"[limit] as stated within every limit: {sound}; refused at "
          f"every seed: {seen}: {'ok' if ok else 'FAILED'}", flush=True)
    print(json.dumps({"ok": ok, "limits": limits, "seeds": args.seed,
                      "readings": readings,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
