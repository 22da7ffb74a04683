#!/usr/bin/env python3
"""The readings that ``decoder_eva``'s limits stand between, at the cell's
own sizes, on the chip, outside any timed window:

    python3 -m benchmark.limit_check_eva --seed <n> [<n> ...]
        [--config evabyte] [--seq-len 32768] [--batch 1] [--part <name> ...]

For each seed the runner's own weights and batch (bf16 parameters from the
program's ``init_params``, ``--batch`` sequences of ``--seq-len`` bytes),
the plain float32 reference's cross-entropy of every position under each
prediction head, and against them the program's loss function with its
readings (``make_loss_fn(with_readings=True)``: the forward pass the train
step differentiates):

* as the configuration states it;
* with one float32 part at a time in bf16 (both poolings of the chunks;
  the statistics of the two softmax states and their merge; the residual
  stream; the heads' logits; the block norms) and with every float32 part
  at once (the configuration computed in the nearest precision below the
  one it states); everything else as stated, the cross-entropies float32.
  The program has no switch for any, so each is a patch of one name while
  the loss function is traced, undone after it. Every value of the part is
  rounded to bf16 where it is computed by ``lax.reduce_precision``
  (``limit_check_afmoe``'s helper): a cast to bf16 and back is no rounding
  on the chip;
* with one piece of the mathematics at a time wrong: the summaries left
  out (a window's own keys alone); every chunk pooled by its mean (the
  pooling vectors ignored); the rotation at theta 10,000.

Three readings each: the loss's relative difference; of the
cross-entropies the root of the mean squared difference and the median of
the absolute difference. ``--part`` runs the named parts alone (beside "as
stated"). Exit code 0 if every reading as stated is within the runner's
three limits and every other part that was run, but those of ``REPORTED``,
is refused by at least one of them at every seed."""
import argparse
import contextlib
import dataclasses
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

from benchmark.limit_check_afmoe import (  # noqa: E402
    F32, _bf16, _einsums_in_bf16, _patched)

# Run and reported, not held to be refused (PERF.md section 6, PR 44):
# the chunk poolings and the heads' logits in bf16 read inside the range
# of the sound program's readings over its seeds (what they read is bf16
# already), and the merge's statistics in bf16 across the limits (7 to 12
# % over the same seed's sound reading, where the seeds alone spread the
# sound readings by 8 %). tests/test_eva.py holds their types in the
# traced step instead. The stream in bf16, the block norms in bf16 and
# every float32 part at once are refused.
REPORTED = ("the chunk poolings in bf16", "the merge's statistics in bf16",
            "the heads' logits in bf16")


def _poolings_in_bf16():
    """``transformer._eva_summaries`` with the scores, their softmax and
    the weighted sums rounded to bf16 where each is computed."""
    from horovod_tpu.models import transformer

    def summaries(k, v, mu, phi, chunk):
        b, t, h, D = k.shape
        kf, vf = (_bf16(x.astype(F32)).reshape(b, t // chunk, chunk, h, D)
                  for x in (k, v))

        def pooled(w, x):
            s = _bf16(jnp.sum(_bf16(kf * _bf16(w)), -1))
            e = _bf16(jnp.exp(_bf16(s - jnp.max(s, axis=2, keepdims=True))))
            a = _bf16(e / _bf16(jnp.sum(e, axis=2, keepdims=True)))
            return _bf16(jnp.sum(_bf16(a[..., None] * x), axis=2)).astype(
                k.dtype)

        return pooled(mu, kf), pooled(phi, vf)

    return mock.patch.object(transformer, "_eva_summaries", summaries)


def _merge_in_bf16():
    """``eva_attention._merged`` on states whose statistics (each set's
    max and sum) are kept in bf16, every value of the combine, the
    normalisation and the joint log-sum-exp rounded to bf16."""
    from horovod_tpu.ops import eva_attention
    from horovod_tpu.ops.pallas_attention import NEG_INF

    def merged(state, state_r):
        acc, m, l = state
        m, l = _bf16(m), _bf16(l)
        if state_r is not None:
            acc_r, m_r, l_r = state_r
            m_r, l_r = _bf16(m_r), _bf16(l_r)
            m_new = jnp.maximum(m, m_r)
            c = _bf16(jnp.exp(_bf16(m - m_new)))
            c_r = jnp.where(m_r > NEG_INF / 2,
                            _bf16(jnp.exp(_bf16(m_r - m_new))), 0.0)
            l = _bf16(_bf16(l * c) + _bf16(l_r * c_r))
            acc = (acc * c.transpose(0, 2, 1)[..., None]
                   + acc_r * c_r.transpose(0, 2, 1)[..., None])
            m = m_new
        o = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return o, _bf16(m + _bf16(jnp.log(jnp.maximum(l, 1e-30))))

    return mock.patch.object(eva_attention, "_merged", merged)


def _stream_in_bf16():
    """``transformer._residual`` with the sum rounded to bf16: the stream
    as the blocks' own type would hold it."""
    from horovod_tpu.models import transformer

    residual = transformer._residual
    return mock.patch.object(
        transformer, "_residual",
        lambda x, out, multiplier: _bf16(residual(x, out, multiplier)))


def _norms_in_bf16():
    """``transformer._rmsnorm_as`` with every value rounded to bf16."""
    from horovod_tpu.models import transformer

    def norm(x, scale, eps, offset, dtype):
        v = _bf16(x.astype(F32))
        ms = _bf16(jnp.mean(_bf16(jnp.square(v)), -1, keepdims=True))
        normed = _bf16(v * _bf16(lax.rsqrt(ms + eps)))
        return _bf16(normed * _bf16(offset + scale.astype(F32))).astype(
            dtype)

    return mock.patch.object(transformer, "_rmsnorm_as", norm)


def _every_part():
    stack = contextlib.ExitStack()
    for patch in (_poolings_in_bf16, _merge_in_bf16, _stream_in_bf16,
                  _norms_in_bf16, lambda: _einsums_in_bf16("btd,dv->btv")):
        stack.enter_context(patch())
    return stack


def _summaries_left_out():
    """The summaries' state as if no query saw any."""
    from horovod_tpu.ops import eva_attention

    merged = eva_attention._merged
    return mock.patch.object(
        eva_attention, "_merged",
        lambda state, state_r: merged(state, None))


def _pooled_by_the_mean():
    from horovod_tpu.models import transformer

    summaries = transformer._eva_summaries.__wrapped__

    def wrong(k, v, mu, phi, chunk):
        return summaries(k, v, jnp.zeros_like(mu), jnp.zeros_like(phi),
                         chunk)

    return mock.patch.object(transformer, "_eva_summaries", wrong)


def _other(**changes):
    return contextlib.nullcontext, lambda cfg: dataclasses.replace(
        cfg, **changes)


# Name of the part -> (the patch around the trace, the program's
# configuration from the stated one).
PARTS = {
    "as stated": _patched(contextlib.nullcontext),
    "the chunk poolings in bf16": _patched(_poolings_in_bf16),
    "the merge's statistics in bf16": _patched(_merge_in_bf16),
    "the stream in bf16": _patched(_stream_in_bf16),
    "the heads' logits in bf16": _patched(
        lambda: _einsums_in_bf16("btd,dv->btv")),
    "block norms in bf16": _patched(_norms_in_bf16),
    "every float32 part in bf16": _patched(_every_part),
    "the summaries left out": _patched(_summaries_left_out),
    "every chunk pooled by its mean": _patched(_pooled_by_the_mean),
    "rotated at theta 10,000": _other(rope_theta=1e4),
}


def main(argv=None):
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_eva
    from benchmark.runners import decoder_eva as runner

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--config", default="evabyte")
    parser.add_argument("--seq-len", type=int, default=32768)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--part", nargs="+")
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    cfg = runner.transformer_config(config)
    model = runner.reference_model(config)
    limits = dict(loss=runner.LOSS_RTOL, nll_rms=runner.NLL_RMS_TOL,
                  nll_median=runner.NLL_MEDIAN_TOL)
    devices = jax.devices()[:1]
    print(f"[limit] {args.config}, {args.batch} x {args.seq_len} bytes, on "
          f"{devices[0].device_kind}; limits {limits}", flush=True)
    mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
    data = NamedSharding(mesh, P("dp", "sp"))

    def inputs(seed):
        """As the runner makes them."""
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        params = transformer.shard_params(
            jax.jit(lambda k: transformer.init_params(cfg, k, n_stages=1))(
                k_params), cfg, mesh)
        tokens, labels = (jax.device_put(x, data) for x in runner.make_batch(
            k_tokens, (args.batch, args.seq_len), config["vocab_size"]))
        return params, tokens, labels

    wants = {}
    for seed in args.seed:
        want = reference_eva.step_readings(*inputs(seed), model)
        wants[seed] = dict(nll=want["nll"], loss=float(want["loss"]))
        print(f"[limit] seed {seed}: reference loss "
              f"{wants[seed]['loss']:.7f}", flush=True)

    all_parts = {part: how for part, how in PARTS.items()
                 if part == "as stated" or not args.part
                 or part in args.part}
    readings = {part: [] for part in all_parts}
    for part, (patch, configured) in all_parts.items():
        jax.clear_caches()  # no trace of another part's is met again
        program = jax.jit(transformer.make_loss_fn(
            configured(cfg), mesh, n_microbatches=1, with_readings=True))
        for seed in args.seed:
            params, tokens, labels = inputs(seed)
            with patch():  # traced at the first seed, under the patch
                loss, got = program(params, tokens, labels)
            want = wants[seed]
            reading = dict(
                loss=abs(float(loss) - want["loss"]) / want["loss"],
                nll_rms=runner.nll_rms(got["token_nll"], want["nll"]),
                nll_median=runner.nll_median(got["token_nll"],
                                             want["nll"]))
            readings[part].append(reading)
            refused = [k for k in limits if reading[k] > limits[k]]
            print(f"[limit] {part:34s} seed {seed}: loss "
                  f"{reading['loss']:.3e}   rms {reading['nll_rms']:.4e}   "
                  f"median {reading['nll_median']:.4e}   refused by "
                  f"{refused}", flush=True)
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
