#!/usr/bin/env python3
"""The readings that ``decoder_afmoe``'s limits stand between, at the
cell's own sizes, on the chip, outside any timed window:

    python3 -m benchmark.limit_check_afmoe --seed <n> [<n> ...]
        [--config trinity-mini] [--seq-len 8192] [--batch 2]

For each seed the runner's own weights and batch (bf16 parameters from the
program's ``init_params``, ``--batch`` sequences of ``--seq-len`` tokens),
the plain float32 reference's cross-entropy of every token and its tokens
per expert, and against them the program's loss
function with its readings (``make_loss_fn(with_readings=True)``: the
forward pass the train step differentiates, with every token's
cross-entropy and the counts):

* as the configuration states it;
* with one float32 part at a time in bf16 (the router's matmul and
  scores, the head's logits, the block norms, the per-head QK-norm), and
  with all four at once, everything else as stated and the cross-entropy
  itself float32. The program has no switch for any of this, so each is a
  patch of one name while the loss function is traced, undone after it.
  Every value of the part is rounded to bf16 where it is computed by
  ``lax.reduce_precision``: a cast to bf16 and back is no rounding on the
  chip, where XLA keeps the excess precision of such a pair;
* with one piece of the mathematics at a time wrong: the window one
  wider, RoPE in the full layer too, no gate, no post-norms, the weights
  normalised over the held picks alone. The first four are the program
  under another ``TransformerConfig``; the last scales the expert
  layer's own result by what the other normalisation would give.

Four readings each: the loss's relative difference, the root of the mean
squared difference of the tokens' cross-entropies, the median of their
absolute difference, the share of the assignments routed to another
expert than the reference routes them.
Exit code 0 if every reading as stated is within the runner's four limits
and every part in ``SEEN`` is refused by at least one of them at every
seed."""
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

F32 = jnp.float32


def _bf16(a):
    """``a`` rounded to bf16's eight significant bits, in its own type."""
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _einsums_in_bf16(*subscripts):
    """``jnp.einsum`` of operands rounded to bf16, its result rounded to
    bf16, for these subscripts; itself for every other."""
    einsum = jnp.einsum

    def patched(spec, *operands, **kwargs):
        if spec not in subscripts:
            return einsum(spec, *operands, **kwargs)
        return _bf16(einsum(spec, *(_bf16(a.astype(F32)) for a in operands),
                            **kwargs))

    return mock.patch.object(jnp, "einsum", patched)


def _router():
    """The router's matmul and its scores in bf16."""
    sigmoid = jax.nn.sigmoid
    stack = contextlib.ExitStack()
    stack.enter_context(_einsums_in_bf16("btd,de->bte"))
    stack.enter_context(mock.patch.object(
        jax.nn, "sigmoid",
        lambda a: _bf16(sigmoid(a)) if a.ndim == 3 else sigmoid(a)))
    return stack


def _norms_in_bf16(*ndims):
    """``transformer._rmsnorm`` in bf16 for operands of these ranks (3:
    the residual stream's block norms; 4: queries and keys by head),
    itself for every other."""
    from horovod_tpu.models import transformer

    rmsnorm = transformer._rmsnorm

    def norm(x, scale, eps):
        if x.ndim not in ndims:
            return rmsnorm(x, scale, eps)
        v = _bf16(x.astype(F32))
        ms = _bf16(jnp.mean(_bf16(jnp.square(v)), -1, keepdims=True))
        normed = _bf16(v * _bf16(lax.rsqrt(ms + eps)))
        return _bf16(normed * _bf16(scale.astype(F32))).astype(x.dtype)

    return mock.patch.object(transformer, "_rmsnorm", norm)


def _held_picks_alone():
    """The expert layer's result as if a token's weights were normalised
    over its *held* picks alone: every token's routed part times (sum of
    all its picked scores) / (sum of its held picked scores)."""
    from horovod_tpu.models import transformer

    moe_layer = transformer.moe_layer

    def wrong(x, params, n_experts, first, **kw):
        y, stats = moe_layer(x, params, n_experts, first, **kw)
        s = jax.nn.sigmoid(jnp.einsum(
            "btd,de->bte", x.astype(F32), params["router"],
            precision=lax.Precision.HIGHEST))
        chosen = lax.top_k(s + params["expert_bias"], kw["top_k"])[1]
        picked = jnp.any(chosen[..., None] == jnp.arange(n_experts), -2)
        w = jnp.where(picked, s, 0.0)
        held = params["wg"].shape[0]
        over_held = jnp.sum(lax.dynamic_slice_in_dim(w, first, held, -1), -1)
        ratio = jnp.sum(w, -1) / jnp.maximum(over_held, 1e-20)
        return (y.astype(F32) * ratio[..., None]).astype(y.dtype), stats

    return mock.patch.object(transformer, "moe_layer", wrong)


def _every_part():
    """The four patches above at once: the program as it would be with
    nothing stated in float32 but the loss."""
    stack = contextlib.ExitStack()
    stack.enter_context(_router())
    # (a later patch of jnp.einsum passes every other subscript on to
    # the earlier one)
    stack.enter_context(_einsums_in_bf16("btd,dv->btv"))
    stack.enter_context(_norms_in_bf16(3, 4))
    return stack


def _other(**fields):
    """The program under a configuration that differs in ``fields``."""
    return contextlib.nullcontext, lambda cfg: dataclasses.replace(
        cfg, **{k: v(cfg) if callable(v) else v for k, v in fields.items()})


def _patched(patch):
    return patch, lambda cfg: cfg


# Name of the part -> (the patch around the trace, the program's
# configuration from the stated one).
PARTS = {
    "as stated": _patched(contextlib.nullcontext),
    "router (matmul and scores) in bf16": _patched(_router),
    "logits (the head's result) in bf16": _patched(
        lambda: _einsums_in_bf16("btd,dv->btv")),
    "block norms in bf16": _patched(lambda: _norms_in_bf16(3)),
    "per-head QK-norm in bf16": _patched(lambda: _norms_in_bf16(4)),
    "every float32 part above in bf16": _patched(_every_part),
    "window one wider": _other(
        sliding_window=lambda cfg: cfg.sliding_window + 1),
    # A layer of kind "attention" takes the model's own switches.
    "RoPE in the full layer too": _other(
        rope=True, layer_types=lambda cfg: tuple(
            "attention" if kind == "full_attention" else kind
            for kind in cfg.kinds)),
    "no gate": _other(attn_gate=False),
    "no post-norms": _other(post_norms=False),
    "weights normalised over the held picks alone": _patched(
        _held_picks_alone),
}


# The parts the limits have to refuse at every seed: every piece of
# mathematics; the router and the block norms in the precision below, and
# every float32 part at once. Not the head's logits alone (rounding them
# to bf16 moves the median by 1 to 2 %, the seeds move it by 5 %) nor the
# per-head QK-norm alone (8 to 13 % over its own seed's sound reading,
# over the limit at 22 seeds of 23 and by too little to hold): PERF.md section
# 6, PR 32. tests/test_afmoe.py holds the types of the head, the router
# and every norm in the traced step instead.
SEEN = ("router (matmul and scores) in bf16", "block norms in bf16",
        "every float32 part above in bf16", "window one wider",
        "RoPE in the full layer too", "no gate", "no post-norms",
        "weights normalised over the held picks alone")


def main(argv=None):
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel.mesh import build_parallel_mesh

    from benchmark import harness, reference_afmoe
    from benchmark.runners import decoder_afmoe, decoder_hybrid

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--config", default="trinity-mini")
    parser.add_argument("--seq-len", type=int, default=8192)
    parser.add_argument("--batch", type=int, default=2)
    args = parser.parse_args(argv)

    harness.enable_compile_cache()
    with open(os.path.join(harness.HERE, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    cfg = decoder_afmoe.transformer_config(config)
    model = decoder_afmoe.reference_model(config)
    limits = dict(loss=decoder_afmoe.LOSS_RTOL,
                  nll_rms=decoder_afmoe.NLL_RMS_TOL,
                  nll_median=decoder_afmoe.NLL_MEDIAN_TOL,
                  moved=decoder_afmoe.MOVED_SHARE_TOL)
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

    reference = jax.jit(lambda p, t, l: reference_afmoe.forward(
        p, t, l, model))
    wants = {}
    for seed in args.seed:
        nll, load = reference(*inputs(seed))
        wants[seed] = (nll, np.asarray(load))
        print(f"[limit] seed {seed}: reference loss "
              f"{float(jnp.mean(nll)):.7f}", flush=True)

    readings = {part: [] for part in PARTS}
    for part, (patch, configured) in PARTS.items():
        jax.clear_caches()  # no trace of another part's is met again
        part_cfg = configured(cfg)
        program = jax.jit(transformer.make_loss_fn(
            part_cfg, mesh, n_microbatches=1, with_readings=True))
        for seed in args.seed:
            params, tokens, labels = inputs(seed)
            params = {k: v for k, v in params.items()
                      if k in transformer._param_specs(part_cfg)}
            with patch():  # traced at the first seed, under the patch
                loss, got = program(params, tokens, labels)
            want, want_load = wants[seed]
            want_loss = float(jnp.mean(want))
            load = np.asarray(got["load"])[part_cfg.num_dense_layers:]
            nll = got["token_nll"]
            reading = dict(
                loss=abs(float(loss) - want_loss) / want_loss,
                nll_rms=decoder_hybrid.nll_rms(nll, want),
                nll_median=decoder_afmoe.nll_median(nll, want),
                moved=float(np.abs(load - want_load).sum() // 2
                            / want_load.sum()))
            readings[part].append(reading)
            refused = [k for k in limits if reading[k] > limits[k]]
            print(f"[limit] {part:46s} seed {seed}: loss "
                  f"{reading['loss']:.3e}   rms {reading['nll_rms']:.4e}   "
                  f"median {reading['nll_median']:.4e}   "
                  f"routed elsewhere {reading['moved']:.5f}   refused by "
                  f"{refused}", flush=True)
            del params

    def refused(reading):
        return any(reading[k] > limits[k] for k in limits)

    sound = not any(map(refused, readings["as stated"]))
    seen = {part: all(map(refused, readings[part]))
            for part in PARTS if part != "as stated"}
    ok = sound and all(seen[part] for part in SEEN)
    print(f"[limit] as stated within every limit: {sound}; refused at "
          f"every seed: {seen}: {'ok' if ok else 'FAILED'}", flush=True)
    print(json.dumps({"ok": ok, "limits": limits, "seeds": args.seed,
                      "readings": readings,
                      "device": devices[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
