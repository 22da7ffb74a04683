"""Runner ``decoder_zaya``: ``models/transformer.py``'s decoder as one chip
of a ZAYA1-8B (``zaya``) deployment holds it: a CCA mixer in every layer,
then experts picked one a token by the ZAYA router's MLP, whose state
crosses layers, while the chip holds a share of them, learned scales on
the residual stream, the router's balancing bias moved by the step, and
the tied head with the loss by blocks of tokens; through
``transformer.make_train_step`` on ``build_parallel_mesh`` (dp over the
cell's chips), the program's own initialiser and optimizer-state helper.
A run starts where every seed does the same work: the bias where the rule
balances the run's own batch (``balanced_bias``), AdamW's rate rising from
zero (``optimizer_of``); the configuration's ``assumed`` has why.
Reads a configuration with ``zaya``'s published keys
(configs/zaya1-8b.json) and a ``token_batches`` traffic file."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_train_step, shard_params, trained)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops_zaya, reference_zaya
from benchmark.runners import decoder_afmoe
from benchmark.runners.decoder_afmoe import _REMAT, nll_median
from benchmark.runners.decoder_hybrid import nll_rms

# System (bf16 parameters, activations and matmul operands; float32
# norms, the q/k norm with its temperature among them, the router's whole
# chain, scores, top-1, bias, the residual scales, the head's logits and
# the loss) against the float32 reference on the first step's weights and
# batch. Everything compared is the timed executable's own first step:
# its loss, every token's cross-entropy of its forward pass
# (``readings["token_nll"]``), its tokens per expert, the bias it left.
# The readings are PERF.md's (section 6, PR 38), all at the balanced
# start (``balanced_bias``): the cell's own runs, ``python3 -m
# benchmark.limit_check_zaya``, which runs the same loss function as
# stated, with its float32 parts in bf16 (each rounded where it is
# computed, by ``lax.reduce_precision``) and with one piece of the
# mathematics at a time wrong, and the same at ten seeds more. A balanced
# router sits on its thresholds: it routes twice as many tokens elsewhere
# under the same rounding as the collapsed one the first limits were read
# on, and every reading below goes with the seed together (correlations
# 0.91 to 0.98 over ten seeds), so the room is what the seeds leave.
#
# (1) The loss of the first training step, relative. Read on the chip
# 0 to 9.9e-6 as stated over thirty-six readings at twenty-nine seeds;
# the limit is the harness's accepted cells' and twenty times the
# largest. The convolutions left out read up to 8.0e-4. It is no limit on
# precision, and hardly one on the mathematics: at initialisation the
# cross-entropy sits near ln 131,136 + 0.4 whatever the layers do.
LOSS_RTOL = 2e-4

# (2) Every token's cross-entropy, as the root of the mean squared
# difference from the reference's over the 16,384 tokens. As stated
# 8.13e-3 to 1.030e-2 (mean 9.1e-3, 6.6 % over the seeds); every float32
# part in bf16 at once 1.117e-2 to 1.289e-2 in eleven readings at ten
# seeds, the router's chain alone 1.04e-2 to 1.27e-2. The limit is 8.8 %
# over the largest sound reading, 3.5 deviations of the seeds' logarithms
# over their mean, and under all but one reading of the parts in bf16
# (seed 5: 1.117e-2, which (3) refuses). The mathematics wrong reads
# 7.7e-2 and more.
NLL_RMS_TOL = 1.12e-2

# (2b) The median over the tokens of the absolute difference. As stated
# 3.87e-3 to 5.23e-3, every float32 part in bf16 5.23e-3 to 6.46e-3: too
# near for a limit with room for a sound run. This one refuses the
# mathematics beside (2) (5.2e-2 and more) at three times the largest
# sound reading; (2) and (3) are the limits that see precision.
NLL_MEDIAN_TOL = 1.5e-2

# (3) Of the 16,384 tokens a layer, how many the float32 reference routes
# to another expert than the program, whose router reads bf16
# activations: over all ten layers, by the experts' counts, as a share.
# As stated 0.00397 to 0.00681 over the same thirty-six readings (mean
# 0.0052, 15 % over the seeds: at balance the counts' differences are
# what is left of flows both ways); every float32 part in bf16 at once
# 0.00805 to 0.00998 in eleven readings at ten seeds, the router's chain
# alone 0.00698 to 0.00944. The limit is 17 % over the largest sound
# reading, 3.1 deviations of the seeds' logarithms over their mean, and
# 0.6 % under the smallest of the parts in bf16 (whose (2) reads
# 1.146e-2): between them (2) and (3) refused every float32 part in bf16
# in each of the eleven readings, and neither would alone with this room
# for a sound run. The q/k norm with its temperature, the head's logits
# or the block norms alone in bf16 read inside the sound range:
# tests/test_zaya.py holds their types in the traced step.
MOVED_SHARE_TOL = 8.0e-3


def transformer_config(config):
    """The program's ``TransformerConfig`` of a configuration file with
    ``zaya``'s published keys."""
    n_layers = config["num_hidden_layers"]
    kinds = set(config["layer_types"][:n_layers])
    rope = config["rope_parameters"]["hybrid"]
    if (config["hidden_act"] != "silu" or not config["tie_word_embeddings"]
            or config["attention_bias"] or config["lm_head_bias"]
            or config["sliding_window"] or kinds != {"hybrid"}
            or config["num_experts_per_tok"] != 1
            or rope["rope_type"] != "default"
            or rope["partial_rotary_factor"]
            != config["partial_rotary_factor"]):
        raise ValueError(
            "decoder_zaya runner: gated SiLU experts one a token, a tied "
            "head, no biases, no window, every layer a CCA block then an "
            "expert block ('hybrid') and plain partial RoPE are what the "
            "program builds")
    first, end = config["experts_held"]
    if end - first != config["num_experts"]:
        raise ValueError("decoder_zaya runner: num_experts counts the "
                         "experts held, experts_held names them")
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"], n_layers=n_layers,
        max_seq=config["max_position_embeddings"],
        layer_types=("cca",) * n_layers, cca_time0=config["cca_time0"],
        cca_time1=config["cca_time1"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]), pos_table=False,
        use_moe=True, n_experts=config["num_experts_published"],
        n_experts_held=config["num_experts"], first_expert_held=first,
        d_expert=config["moe_intermediate_size"], moe_top_k=1,
        router_hidden=config["router_hidden_size"],
        expert_bias_rate=float(config["assumed"]["bias_rate"]),
        residual_scales=True, tie_embeddings=True,
        head_block=config["head_block_tokens"], norm="rmsnorm",
        norm_eps=config["rms_norm_eps"], remat=_REMAT[config["recompute"]],
        remat_keeps=tuple(config["recompute_keeps"]),
        dtype=jnp.dtype(config["dtype"]))


def reference_model(config):
    """What ``reference_zaya`` needs of the configuration."""
    rope = config["rope_parameters"]["hybrid"]
    return dict(
        num_hidden_layers=config["num_hidden_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(rope["rope_theta"]),
        rotated=int(config["partial_rotary_factor"] * config["head_dim"]),
        first_expert_held=config["experts_held"][0],
        load_balance_coeff=float(config["assumed"]["bias_rate"]))


def optimizer_of(opt_cfg):
    """AdamW whose rate rises in a straight line from zero over
    ``warmup_steps`` steps, as a run's first steps do: at 4,000 the window
    lies in the first hundredth of the rise."""
    if opt_cfg["name"] != "adamw":
        raise ValueError(f"decoder_zaya runner: optimizer {opt_cfg!r}")
    return optax.adamw(optax.linear_schedule(
        0.0, opt_cfg["learning_rate"], opt_cfg["warmup_steps"]))


def _balance(p, rates):
    """The bias [E] the balancing rule (``reference_zaya.updated_bias``)
    leaves, from zero, after one application on the scores p [B, T, E]
    for each of ``rates``, in order."""
    E = p.shape[-1]

    def apply(bias, rate):
        picked = jnp.argmax(p + bias, -1)[..., None] == jnp.arange(E)
        return reference_zaya.updated_bias(
            bias, jnp.sum(picked, axis=(0, 1)), rate), None

    return lax.scan(apply, jnp.zeros(E, jnp.float32), rates)[0]


def _layer_balanced(x, r_prev, lp, *, model, rates):
    """``reference_zaya.layer`` with the layer's bias set by ``_balance``
    on its own scores before its experts run: (x after it, r_l, the
    bias)."""
    ref, eps = reference_zaya, model["rms_norm_eps"]
    a, b, c = ref._f32(lp["res1"])
    x = a * x + b + c * ref.attention(ref._rms(x, lp["ln1"], eps), lp, model)
    u = ref._rms(x, lp["ln2"], eps)
    bias = _balance(ref.router(u, lp, r_prev, model)[0], rates)
    out, r, _ = ref.expert_layer(u, {**lp, "expert_bias": bias}, r_prev,
                                 model)
    a, b, c = ref._f32(lp["res2"])
    return a * x + b + c * out, r, bias


def balanced_bias(params, tokens, model, start):
    """The balancing bias, shaped as ``params["expert_bias"]``, at which
    every layer's router gives each expert its share of ``tokens``, layer
    by layer from the first: the state a deployment's rule has reached
    long before a step is worth timing, where a seeded router alone sends
    a layer's tokens to one or two experts and which half of them this
    chip holds is the seed's accident. ``start`` is the configuration's
    ``bias_start``: the rule's rates, from ``first_rate`` down to
    ``last_rate`` in equal ratios over ``applications``. A layer at a
    time, as ``reference_zaya.forward``."""
    rates = np.geomspace(start["first_rate"], start["last_rate"],
                         start["applications"])
    run = jax.jit(functools.partial(_layer_balanced, model=model,
                                    rates=jnp.asarray(rates, jnp.float32)))
    with jax.default_matmul_precision("highest"):
        x = reference_zaya._f32(params["embed"][tokens])
        r = jnp.zeros(x.shape[:2] + (params["r_down"].shape[-1],),
                      jnp.float32)
        biases = []
        for at in range(model["num_hidden_layers"]):
            x, r, bias = run(x, r, reference_zaya.layer_leaves(params, at))
            biases.append(bias)
    like = params["expert_bias"]
    return jax.device_put(jnp.stack(biases).reshape(like.shape),
                          like.sharding)


def model_flops_per_token(cfg, seq_len, held_rows_per_token):
    return flops_zaya.zaya_train_flops_per_token(
        d=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads,
        head_dim=cfg.d_head, time0=cfg.cca_time0, time1=cfg.cca_time1,
        router_hidden=cfg.router_hidden, n_experts=cfg.n_experts,
        d_expert=cfg.d_expert, n_layers=cfg.n_layers, vocab_rows=cfg.vocab,
        seq_len=seq_len, held_rows_per_token=held_rows_per_token)


class Job(decoder_afmoe.Job):
    """``decoder_afmoe.Job``'s ``lower``, ``step`` and ``close`` (a biased
    step that returns its readings) around this model's set-up and its
    own comparison."""

    def __init__(self, config, traffic, devices, seed):
        self.cfg = transformer_config(config)
        self.model = reference_model(config)
        cfg = self.cfg
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        # What the kernel-layer metrics need: one layer's shapes on one
        # chip, and how many layers run them per step. The held rows are
        # the first step's own count (``compare_reference``).
        self.cca = dict(batch=traffic["batch_per_chip"], heads=cfg.n_heads,
                        kv_heads=cfg.kv_heads, seq_len=self.seq_len,
                        head_dim=cfg.d_head, layers=cfg.n_layers,
                        itemsize=cfg.dtype.itemsize)
        self.moe_share = dict(d=cfg.d_model, d_expert=cfg.d_expert,
                              experts_held=cfg.experts_held,
                              layers=cfg.n_layers,
                              itemsize=cfg.dtype.itemsize, rows_held=None)
        self.zaya_head = dict(tokens=traffic["batch_per_chip"]
                              * self.seq_len, d=cfg.d_model,
                              vocab_rows=cfg.vocab)
        self.model_flops_per_step = None
        self.moe_held_rows_share = None

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        optimizer = optimizer_of(config["optimizer"])
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        # Weights and the batch are made on the device from the seed, each
        # in one jitted call, in the type they are trained in.
        self.params = shard_params(
            jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params),
            cfg, mesh)
        # The balancing bias is no trained parameter: no moments for it.
        self.opt_state = init_opt_state(optimizer, trained(self.params),
                                        mesh)
        data = NamedSharding(mesh, P("dp", "sp"))
        vocab, shape = config["vocab_size"], (self.batch, self.seq_len)

        def make_batch(k):
            tokens = jax.random.randint(k, shape, 0, vocab, jnp.int32)
            return tokens, jnp.roll(tokens, -1, axis=1)

        self.tokens, self.labels = jax.jit(
            make_batch, out_shardings=(data, data))(k_tokens)
        # The bias starts where the rule has balanced this batch.
        self.params["expert_bias"] = balanced_bias(
            self.params, self.tokens, self.model, config["bias_start"])
        self.step_fn = make_train_step(cfg, optimizer, mesh,
                                       n_microbatches=1)
        self.compiled = None
        # The last step's tokens per expert by layer and every token's
        # cross-entropy of its forward pass.
        self.readings = None
        self._want = None

    def prepare_reference(self):
        """Before the first step (which donates the parameters): what the
        plain float32 reference makes of these weights on the whole
        batch."""
        want = reference_zaya.step_readings(self.params, self.tokens,
                                            self.labels, self.model)
        self._want = dict(
            loss=float(want["loss"]), load=np.asarray(want["load"]),
            nll=want["nll"],
            bias_before=np.asarray(self.params["expert_bias"]))

    def compare_reference(self, first_loss):
        """After the timed executable's first step: its loss, every
        token's cross-entropy of its forward pass, its own counts and the
        bias it left, each against the reference or the rule."""
        cfg, want = self.cfg, self._want
        load = np.asarray(self.readings["load"])
        got_nll = jax.device_put(self.readings["token_nll"],
                                 want["nll"].sharding)
        rms = nll_rms(got_nll, want["nll"])
        median = nll_median(got_nll, want["nll"])
        assignments = self.samples_per_step
        first = cfg.first_expert_held
        held = load[:, first:first + cfg.experts_held]
        self.moe_share["rows_held"] = float(held.sum(axis=1).mean())
        self.moe_held_rows_share = float(held.sum() / load.sum())
        self.model_flops_per_step = self.samples_per_step * \
            model_flops_per_token(
                cfg, self.seq_len,
                self.moe_share["rows_held"] / self.samples_per_step)
        print(f"[bench] tokens per expert, the first step's own counts: "
              f"on held experts {held.sum(axis=1).tolist()} a layer of "
              f"{assignments} assignments (share "
              f"{self.moe_held_rows_share:.5f}; a half at balance), over "
              f"all {cfg.n_experts} experts max {load.max(axis=1).tolist()} "
              f"min {load.min(axis=1).tolist()}; windows "
              f"{np.asarray(self.readings['windows']).tolist()}",
              flush=True)

        err = abs(first_loss - want["loss"]) / abs(want["loss"])
        sums = load.sum(axis=1)
        moved = int(np.abs(want["load"] - load).sum()) // 2
        moved_share = moved / float(load.sum())
        by_rule = np.asarray(reference_zaya.updated_bias(
            want["bias_before"], load.reshape(want["bias_before"].shape),
            cfg.expert_bias_rate))
        bias_err = float(np.abs(np.asarray(self.params["expert_bias"])
                                - by_rule).max())

        def within(what, got, tol, **more):
            return dict(what=what, got=got, want=0.0, tol=tol,
                        ok=bool(got <= tol), **more)

        return [
            dict(what="first-step loss vs float32 reference",
                 got=first_loss, want=want["loss"], rel_err=err,
                 tol=LOSS_RTOL,
                 ok=bool(np.isfinite(err) and err <= LOSS_RTOL)),
            within("every token's cross-entropy of the first step vs "
                   "float32 reference, rms of the difference",
                   rms, NLL_RMS_TOL),
            within("the same, the median of the absolute difference",
                   median, NLL_MEDIAN_TOL),
            dict(what="tokens per expert of every layer sum to the tokens "
                      "(one a token, nothing dropped)",
                 got=sums.tolist(), want=assignments, tol=0,
                 ok=bool((sums == assignments).all())),
            within("assignments the float32 reference routes elsewhere, "
                   "share of all", moved_share, MOVED_SHARE_TOL,
                   moved=moved, of=int(load.sum())),
            within("the bias after the first step vs the rule on the "
                   "step's own counts, largest difference", bias_err,
                   1e-7)]


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder_zaya runner takes token_batches "
                         f"traffic, not {traffic['kind']!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len exceeds the configuration's "
                         "max_position_embeddings")
    return Job(config, traffic, devices, seed)
