"""Runner ``decoder_afmoe``: ``models/transformer.py``'s decoder as one
chip of a Trinity (``afmoe``) deployment holds it: sliding-window and
full attention layers in one stack, gated attention, per-head QK-norm,
four norms a layer, a leading dense layer, then expert layers whose
sigmoid router scores all experts while the chip holds a share of them
and computes that share's part, a shared expert, and the router's
balancing bias moved by the step; through
``transformer.make_train_step`` on ``build_parallel_mesh`` (dp over the
cell's chips), the program's own initialiser and optimizer-state helper.
Reads a configuration with ``afmoe``'s published keys
(configs/trinity-mini.json) and a ``token_batches`` traffic file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_train_step, shard_params, trained)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops_afmoe, reference_afmoe
from benchmark.runners.decoder_hybrid import nll_rms

# System (bf16 parameters, activations and matmul operands; float32
# norms, router, scores, top-k, bias, head, logits and loss) against the
# float32 reference on the first step's weights and batch. Everything
# compared is the timed executable's own first step: its loss, every
# token's cross-entropy of its forward pass (``readings["token_nll"]``),
# its tokens per expert, the bias it left. The readings are PERF.md's
# (section 6, PR 32): the cell's own runs, and ``python3 -m
# benchmark.limit_check_afmoe``, which runs the same loss function as
# stated, with its float32 parts in bf16 (each rounded where it is
# computed, by ``lax.reduce_precision``: XLA keeps no excess precision
# through that), and with one piece of the mathematics at a time wrong.
#
# (1) The loss of the first training step, relative. Read on the chip
# up to 6.9e-5 as stated over forty readings (the chip's float32 log is
# 1e-4 coarse in absolute terms on a loss of 10.6, as in the other
# decoder cells); the limit is three times the largest. It refuses
# missing post-norms, no gate and weights normalised over the held picks
# alone at some seeds and nothing at every seed. It is no limit on
# precision, and hardly one on the mathematics: at initialisation the
# loss sits within 0.01 of ln 25,024 whatever the layers do.
LOSS_RTOL = 2e-4

# (2) Every token's cross-entropy, as the root of the mean squared
# difference from the reference's over the 16,384 tokens. As stated
# 3.2e-2 to 3.9e-2, twenty times what ``granite-h-t8192`` reads at the
# same width, and nearly all of it from the few tokens of (3): the
# reference picks another expert for them, the expert is held or is not,
# and the post-norm scales what is left of the branch back to full size;
# it swings with their number. It refuses RoPE in the full layer (0.115
# to 0.136), no gate (0.49 to 0.50), no post-norms (1.18 to 1.21), the
# other normalisation (0.47 to 0.51), and a fault that reaches few
# tokens, which a median would not see; the window one wider (4.6e-2 to
# 5.3e-2) and the router in bf16 (4.2e-2 to 4.9e-2) read too near the
# sound readings for this one: (2b) and (3) refuse them.
NLL_RMS_TOL = 6e-2

# (2b) The median over the tokens of the absolute difference. The few
# per cent of the tokens that a moved assignment reaches cannot move a
# median, so what it reads is the rounding of the values, and a limit on
# it has room where one on an rms has none. As stated 7.08e-3 to 7.50e-3
# over thirty-two readings (twenty-three seeds of the limit check, and
# at nine of them the cell's own first step); the limit is 5.3 % over the
# largest. Over it, at each of the twenty-three seeds: the block norms in
# bf16 8.16e-3 to 8.68e-3, every float32 part in bf16 at once 9.46e-3 to
# 1.01e-2, the window one wider 1.19e-2 to 1.25e-2, and every other
# piece of wrong mathematics at 6.5e-2 and more. The per-head QK-norm
# alone in bf16 reads 7.89e-3 to 8.35e-3, over the limit at twenty-two
# seeds and by a hair, and the head's logits alone 7.22e-3 to 7.62e-3,
# not over it: tests/test_afmoe.py holds those two by the types of the
# traced step.
NLL_MEDIAN_TOL = 7.9e-3

# (3) Of the 8 x 16,384 assignments a layer, how many the float32
# reference routes to another expert than the program, whose router
# reads bf16 activations: over all expert layers, as a share. As stated
# 0.00183 to 0.00215 (OLMoE's softmax router: 0.002); the limit is 40 %
# over the largest. The router's matmul and scores in bf16 0.00672 to
# 0.00737 (a bf16 score near 0.8 is one of 256 steps of 0.004, so picks
# tie), every float32 part at once 0.00684 to 0.00742, RoPE in the full
# layer 0.0101 to 0.0139, no gate 0.053 to 0.064, no post-norms 0.25 to
# 0.29, the other normalisation 0.026 to 0.037.
MOVED_SHARE_TOL = 3e-3

# The configuration's ``recompute`` as ``TransformerConfig.remat``.
_REMAT = {"none": False, "layers": True}


def layer_types(config):
    """The layers this configuration runs: ``num_hidden_layers`` of the
    published pattern from ``first_layer_run``."""
    first = config["first_layer_run"]
    return tuple(config["layer_types"][
        first:first + config["num_hidden_layers"]])


def transformer_config(config):
    """The program's ``TransformerConfig`` of a configuration file with
    ``afmoe``'s published keys."""
    if (config["hidden_act"] != "silu" or config["score_func"] != "sigmoid"
            or config["tie_word_embeddings"] or config["rope_scaling"]
            or not config["mup_enabled"]
            or (config["n_group"], config["topk_group"],
                config["num_expert_groups"],
                config["num_limited_groups"]) != (1, 1, 1, 1)):
        raise ValueError(
            "decoder_afmoe runner: gated SiLU, a sigmoid router over one "
            "group of experts, an untied head, plain RoPE and the "
            "embedding multiplier sqrt(hidden_size) are what the program "
            "builds")
    d = config["hidden_size"]
    first, end = config["experts_held"]
    if end - first != config["num_experts"]:
        raise ValueError("decoder_afmoe runner: num_experts counts the "
                         "experts held, experts_held names them")
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=d,
        n_heads=config["num_attention_heads"], d_head=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        max_seq=config["max_position_embeddings"],
        layer_types=layer_types(config),
        sliding_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]), pos_table=False,
        use_moe=True, num_dense_layers=config["num_dense_layers"],
        n_experts=config["num_experts_published"],
        n_experts_held=config["num_experts"], first_expert_held=first,
        d_expert=config["moe_intermediate_size"],
        moe_top_k=config["num_experts_per_tok"],
        moe_score_func="sigmoid", norm_topk_prob=config["route_norm"],
        route_scale=float(config["route_scale"]),
        n_shared_experts=config["num_shared_experts"],
        expert_bias_rate=float(config["load_balance_coeff"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], qk_norm="head",
        attn_gate=True, post_norms=True, gated_mlp=True,
        embedding_multiplier=float(d) ** 0.5,
        remat=_REMAT[config["recompute"]],
        remat_keeps=tuple(config["recompute_keeps"]),
        dtype=jnp.dtype(config["dtype"]))


def reference_model(config):
    """What ``reference_afmoe`` needs of the configuration."""
    return dict(layer_types=layer_types(config),
                embedding_multiplier=float(config["hidden_size"]) ** 0.5,
                first_expert_held=config["experts_held"][0],
                **{k: config[k] for k in (
                    "num_dense_layers", "sliding_window", "rope_theta",
                    "rms_norm_eps", "num_experts_per_tok", "route_norm",
                    "route_scale", "load_balance_coeff")})


def nll_median(got, want):
    """The median over the tokens of the absolute difference of two
    [B, T] cross-entropies."""
    return float(jnp.median(jnp.abs(got - want)))


class Job:
    sample_unit = "tokens"

    def __init__(self, config, traffic, devices, seed):
        self.cfg = transformer_config(config)
        self.model = reference_model(config)
        cfg = self.cfg
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        sliding = cfg.kinds.count("sliding_attention")
        # What the kernel-layer metrics need: one layer's shapes on one
        # chip, and how many layers run them per step. The held rows are
        # the first step's own count (``compare_reference``).
        self.swa = dict(batch=traffic["batch_per_chip"], heads=cfg.n_heads,
                        seq_len=self.seq_len, head_dim=cfg.d_head,
                        window=cfg.sliding_window, layers=sliding,
                        itemsize=cfg.dtype.itemsize)
        self.moe_share = dict(d=cfg.d_model, d_expert=cfg.d_expert,
                              experts_held=cfg.experts_held,
                              layers=cfg.ffn_kinds.count("moe"),
                              itemsize=cfg.dtype.itemsize, rows_held=None)
        self.model_flops_per_step = None
        self.moe_held_rows_share = None
        self.moe_held_max_over_mean = None

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "adamw":
            raise ValueError(f"decoder_afmoe runner: optimizer {opt_cfg!r}")
        optimizer = optax.adamw(opt_cfg["learning_rate"])
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
        self.step_fn = make_train_step(cfg, optimizer, mesh,
                                       n_microbatches=1)
        self.compiled = None
        # The last step's tokens per expert by layer and every token's
        # cross-entropy of its forward pass.
        self.readings = None
        self._want = None

    def lower(self):
        return self.step_fn.lower(self.params, self.opt_state, self.tokens,
                                  self.labels)

    def step(self):
        self.params, self.opt_state, loss, self.readings = self.compiled(
            self.params, self.opt_state, self.tokens, self.labels)
        return loss

    def prepare_reference(self):
        """Before the first step (which donates the parameters): what the
        plain float32 reference makes of these weights on the whole
        batch."""
        one = self.tokens.sharding.mesh.devices.flat[0]
        put = lambda x: jax.device_put(x, one)
        model = self.model
        ref = jax.jit(lambda p, t, l: reference_afmoe.step_readings(
            p, t, l, model))
        want = ref(jax.tree_util.tree_map(put, self.params),
                   put(self.tokens), put(self.labels))
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
        routed = load[cfg.num_dense_layers:]
        assignments = cfg.moe_top_k * self.samples_per_step
        first = cfg.first_expert_held
        held = routed[:, first:first + cfg.experts_held]
        self.moe_share["rows_held"] = float(held.sum(axis=1).mean())
        self.moe_held_rows_share = float(held.sum() / routed.sum())
        self.moe_held_max_over_mean = float(
            (held.max(axis=1) / held.mean(axis=1)).max())
        self.model_flops_per_step = self.samples_per_step * \
            flops_afmoe.afmoe_train_flops_per_token(
                d=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads,
                head_dim=cfg.d_head, d_ff=cfg.d_ff, d_expert=cfg.d_expert,
                n_experts=cfg.n_experts,
                n_shared_experts=cfg.n_shared_experts,
                layer_types=cfg.kinds,
                num_dense_layers=cfg.num_dense_layers,
                sliding_window=cfg.sliding_window, vocab_rows=cfg.vocab,
                seq_len=self.seq_len,
                held_rows_per_token=self.moe_share["rows_held"]
                / self.samples_per_step)
        print(f"[bench] tokens per expert, the first step's own counts: "
              f"on held experts {held.sum(axis=1).tolist()} a layer of "
              f"{assignments} assignments (share "
              f"{self.moe_held_rows_share:.5f}; an eighth at balance), "
              f"the held experts' largest group over their mean by layer "
              f"{[round(float(x), 4) for x in held.max(axis=1) / held.mean(axis=1)]}"
              f", over all {cfg.n_experts} experts max "
              f"{routed.max(axis=1).tolist()} min "
              f"{routed.min(axis=1).tolist()}", flush=True)

        err = abs(first_loss - want["loss"]) / abs(want["loss"])
        sums = routed.sum(axis=1)
        moved = int(np.abs(want["load"] - routed).sum()) // 2
        moved_share = moved / float(routed.sum())
        by_rule = np.asarray(reference_afmoe.updated_bias(
            want["bias_before"], routed.reshape(want["bias_before"].shape),
            cfg.expert_bias_rate))
        bias_err = float(np.abs(np.asarray(self.params["expert_bias"])
                                - by_rule).max())
        return [
            dict(what="first-step loss vs float32 reference",
                 got=first_loss, want=want["loss"], rel_err=err,
                 tol=LOSS_RTOL,
                 ok=bool(np.isfinite(err) and err <= LOSS_RTOL)),
            dict(what="every token's cross-entropy of the first step vs "
                      "float32 reference, rms of the difference",
                 got=rms, want=0.0, tol=NLL_RMS_TOL,
                 ok=bool(rms <= NLL_RMS_TOL)),
            dict(what="the same, the median of the absolute difference",
                 got=median, want=0.0, tol=NLL_MEDIAN_TOL,
                 ok=bool(median <= NLL_MEDIAN_TOL)),
            dict(what="tokens per expert of every expert layer sum to "
                      "top_k x tokens (nothing dropped), a dense layer's "
                      "to none",
                 got=sums.tolist(), want=assignments, tol=0,
                 ok=bool((sums == assignments).all()
                         and not load[:cfg.num_dense_layers].any())),
            dict(what="assignments the float32 reference routes "
                      "elsewhere, share of all",
                 got=moved_share, moved=moved, of=int(routed.sum()),
                 want=0.0, tol=MOVED_SHARE_TOL,
                 ok=bool(moved_share <= MOVED_SHARE_TOL)),
            dict(what="the bias after the first step vs the rule on the "
                      "step's own counts, largest difference",
                 got=bias_err, want=0.0, tol=1e-7,
                 ok=bool(bias_err <= 1e-7))]

    def close(self):
        pass


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder_afmoe runner takes token_batches "
                         f"traffic, not {traffic['kind']!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len exceeds the configuration's "
                         "max_position_embeddings")
    return Job(config, traffic, devices, seed)
