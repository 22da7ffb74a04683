"""Runner ``decoder_ling``: ``models/transformer.py``'s decoder as one chip
of a Ling-3.0-flash (``bailing_hybrid``) deployment holds it: Kimi Delta
Attention in five layers of six and latent attention (no query rank, a
value width of its own, QK-norm a head, one gate a head) in the sixth, a
leading dense layer, then expert layers whose sigmoid router scores all 512
experts in 8 groups of which 4 are kept while the chip holds a share, a
shared expert, the router's balancing bias moved by the step, and one
multi-token-prediction module whose mixer is latent attention; through
``transformer.make_train_step`` on ``build_parallel_mesh`` (dp over the
cell's chips), the program's own initialiser and optimizer-state helper.
Reads a configuration with ``bailing_hybrid``'s published keys
(configs/ling-3.0-flash.json) and a ``token_batches`` traffic file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_train_step, shard_params, trained)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops_ling, reference_ling
from benchmark.runners import decoder_glm_lite
from benchmark.runners.decoder_afmoe import _REMAT, nll_median
from benchmark.runners.decoder_hybrid import nll_rms

# System (bf16 parameters, activations and matmul operands; float32 norms,
# decays, gates' sigmoids, the scan's state and its solve, router, scores,
# top-k, bias, both heads, logits and both losses) against the float32
# reference on the first step's weights and batch. Everything compared is
# the timed executable's own first step: its loss, every token's two
# cross-entropies of its forward pass, its tokens per expert, the biases it
# left. The readings are PERF.md's (section 6, PR 47): the cell's own runs,
# and ``python3 -m benchmark.limit_check_ling``, which runs the same loss
# function as stated, with its float32 parts in bf16 (each rounded where it
# is computed) and with one piece of the mathematics at a time wrong.
#
# The sound readings are thirteen at ten seeds (ten runs of the cell, three
# seeds of the limit check), the others three seeds of the limit check.
#
# (1) The loss of the first training step (main + 0.3 x module), relative.
# Read on the chip 1.6e-6 to 5.6e-5 as stated (the chip's float32 log is
# 1e-4 coarse in absolute terms on a loss of 13.5, as in the other decoder
# cells); the limit is the harness's accepted cells' and 3.6 times the
# largest. The module's loss weight zero reads 0.23. No limit on
# precision, and hardly one on the mathematics: at initialisation each
# cross-entropy sits near ln 19,648 + 0.5 whatever the layers do.
LOSS_RTOL = 2e-4

# (2) Every token's main cross-entropy, as the root of the mean squared
# difference from the reference's over the 16,384 tokens. As stated 4.78e-2
# to 5.72e-2 (the seeds move it by a fifth; mean 5.4e-2, the limit four
# standard deviations over it); every float32 part in bf16 at once 6.93e-2
# to 7.22e-2; the limit is 14 % over the largest sound reading and 6 %
# under the smallest of those. It refuses the mathematics: the decay's floor at -1 in place of
# -5 reads 1.06, no gate a head on the latent mixers 0.14 to 0.15, the
# selection over one group of experts 0.105 to 0.120. (Three times
# ``glm-4.7-flash``'s readings: seven layers of d 2,560 against five of
# 2,048, and the scan's matmuls take bf16 operands: keys under their
# decays, the solved updates, the state where it is read.)
NLL_RMS_TOL = 6.5e-2

# (2b) The median over the tokens of the absolute difference: the limit
# that sees precision. As stated 2.673e-2 to 3.165e-2; with every float32
# part in bf16 at once (every norm, the L2 norms and the gated norm of a
# KDA head, the decays, the scan's state from chunk to chunk, the router,
# both heads' logits: the nearest precision below the stated one) 3.996e-2
# to 4.205e-2 at three seeds, 31 to 36 % over the same seed's sound
# reading. The limit is 12 % over the largest sound reading and 11 % under
# the smallest of those. One part alone in bf16 stays under it: the decays
# 3.36e-2 to 3.44e-2, the router 3.34e-2 to 3.38e-2 ((4) refuses that one),
# the scan's state 3.06e-2 to 3.11e-2, inside the sound readings' own
# spread; tests/test_ling.py holds the decays', gates' and norms' types in
# the traced step. Nor does it see the latent softmax's scale at
# initialisation (128^-1/2 in place of 192^-1/2 reads 3.08e-2 to 3.12e-2:
# two mixers of eight, whose scores are still flat); tests/test_ling.py
# holds the mixer to the reference in float32.
NLL_MEDIAN_TOL = 3.55e-2

# (3), (3b) The same two of the module's cross-entropy (of t_{i+2}), whose
# hidden states passed one more layer. The rms as stated 4.10e-2 to
# 4.93e-2, every part in bf16 5.93e-2 to 6.14e-2; the median as stated
# 2.167e-2 to 2.577e-2, every part in bf16 3.275e-2 to 3.400e-2: each limit
# 13 % over the largest sound reading, four standard deviations of the
# seeds' over their mean, and 6 and 11 % under the smallest of all parts at
# once.
MTP_NLL_RMS_TOL = 5.6e-2
MTP_NLL_MEDIAN_TOL = 2.92e-2

# (4) Of the 8 x 16,384 assignments a layer, how many the float32 reference
# routes to another expert than the program, whose router reads bf16
# activations: over all expert layers, the module's among them, as a share.
# As stated 0.00675 to 0.00726; the router's matmul and scores in bf16
# 0.01432 to 0.01462, every part at once 0.01427 to 0.01483; the limit is
# 45 % over the largest sound reading and 26 % under the smallest of the
# router's. The selection over one group reads 0.0200 to 0.0214. (Three
# times the other expert cells' share: 512 scores a token lie closer
# together than 64 or 128, and a group's two largest decide whether its 64
# experts can be picked at all.)
MOVED_SHARE_TOL = 1.05e-2


def transformer_config(config):
    """The program's ``TransformerConfig`` of a configuration file with
    ``bailing_hybrid``'s published keys."""
    if (config["hidden_act"] != "silu" or config["topk_method"] != "noaux_tc"
            or config["score_function"] != "sigmoid"
            or config["tie_word_embeddings"] or config["rope_scaling"]
            or config["use_bias"] or config["use_qkv_bias"]
            or not config["norm_topk_prob"] or config["q_lora_rank"]
            or not config["use_qk_norm"] or not config["kda_safe_gate"]
            or not config["no_kda_lora"] or config["use_kda_lora"]
            or not config["linear_silu"] or config["group_norm_size"] != 1
            or config["num_kv_heads_for_linear_attn"]
            or config["mtp_use_kda"] or config["value_norm"]
            or config["up_proj_norm"] or config["use_nGPT"]
            or config["scale_router_input"] or config["use_mla_nope"]
            or not config["moe_router_enable_expert_bias"]
            or config["gated_attention_proj_granularity_type"] != "head_wise"
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["rotary_dim"] != config["qk_rope_head_dim"]
            or config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                         + config["qk_rope_head_dim"])
            or config["moe_shared_expert_intermediate_size"]
            != config["moe_intermediate_size"]):
        raise ValueError(
            "decoder_ling runner: gated SiLU, a group-limited sigmoid router "
            "with a selection bias and normalised weights, an untied head, "
            "no biases, KDA with full-rank gates, the safe gate and SiLU "
            "behind its convolutions, latent attention with no query rank, "
            "QK-norm and one gate a head, and a multi-token-prediction "
            "module whose mixer is latent attention are what the program "
            "builds")
    first_layer = config["layers_run_published"][0]
    last = first_layer + config["num_hidden_layers"] - 1
    limits = (config["expert_swiglu_limit_list"][first_layer:last + 1]
              + config["share_expert_swiglu_limit_list"][
                  first_layer:last + 1])
    if any(limits):
        raise ValueError("decoder_ling runner: an expert's clamp "
                         "(expert_swiglu_limit_list) in a layer kept is not "
                         "built")
    # The group's full layer is its last (assumed.full_layer_rule).
    group = config["layer_group_size"]
    kinds = tuple("latent_attention" if (i + 1) % group == 0 else "kda"
                  for i in range(first_layer, last + 1))
    if kinds != tuple(config["layer_types"]):
        raise ValueError(f"decoder_ling runner: layer_types "
                         f"{config['layer_types']} are not the published "
                         f"layers {first_layer} to {last}'s kinds {kinds}")
    first, end = config["experts_held"]
    if end - first != config["num_experts"]:
        raise ValueError("decoder_ling runner: num_experts counts the "
                         "experts held, experts_held names them")
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], d_head=config["head_dim"],
        d_ff=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        max_seq=config["max_position_embeddings"], layer_types=kinds,
        mtp_layer_type=config["mtp_layer_type"],
        kda_head_dim=config["head_dim"],
        kda_conv=config["short_conv_kernel_size"],
        kda_gate_floor=float(config["kda_lower_bound"]),
        kda_chunk=config["kda_chunk"],
        q_lora_rank=0, kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], qk_norm="head", attn_gate="head",
        rope_theta=float(config["rope_theta"]), pos_table=False,
        use_moe=True, num_dense_layers=config["first_k_dense_replace"],
        n_experts=config["num_experts_published"],
        n_experts_held=config["num_experts"], first_expert_held=first,
        d_expert=config["moe_intermediate_size"],
        moe_top_k=config["num_experts_per_tok"], moe_score_func="sigmoid",
        moe_n_group=config["n_group"], moe_topk_group=config["topk_group"],
        norm_topk_prob=True,
        route_scale=float(config["routed_scaling_factor"]),
        n_shared_experts=config["num_shared_experts"],
        expert_bias_rate=float(config["assumed"]["bias_rate"]),
        n_mtp_modules=config["num_nextn_predict_layers"],
        mtp_loss_weight=float(config["assumed"]["mtp_loss_weight"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], gated_mlp=True,
        remat=_REMAT[config["recompute"]],
        remat_keeps=tuple(config["recompute_keeps"]),
        dtype=jnp.dtype(config["dtype"]))


def reference_model(config):
    """What ``reference_ling`` needs of the configuration."""
    return dict(
        layer_types=tuple(config["layer_types"]),
        mtp_layer_type=config["mtp_layer_type"],
        num_dense_layers=config["first_k_dense_replace"],
        kda_gate_floor=float(config["kda_lower_bound"]),
        route_scale=float(config["routed_scaling_factor"]),
        first_expert_held=config["experts_held"][0],
        mtp_loss_weight=float(config["assumed"]["mtp_loss_weight"]),
        load_balance_coeff=float(config["assumed"]["bias_rate"]),
        **{k: config[k] for k in (
            "kv_lora_rank", "qk_nope_head_dim", "rope_theta", "rms_norm_eps",
            "n_group", "topk_group", "num_experts_per_tok")})


def model_flops_per_token(cfg, seq_len, held_rows_per_token):
    return flops_ling.ling_train_flops_per_token(
        d=cfg.d_model, n_heads=cfg.n_heads, head_dim=cfg.kda_head_dim,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        d_ff=cfg.d_ff, d_expert=cfg.d_expert, n_experts=cfg.n_experts,
        n_shared_experts=cfg.n_shared_experts, layer_types=cfg.kinds,
        mtp_layer_type=cfg.mtp_kind, num_dense_layers=cfg.num_dense_layers,
        n_mtp_modules=cfg.n_mtp_modules, vocab_rows=cfg.vocab,
        seq_len=seq_len, held_rows_per_token=held_rows_per_token)


class Job(decoder_glm_lite.Job):
    """``decoder_glm_lite.Job``'s ``lower``, ``step``, ``close`` and
    ``biases`` around this model's set-up and its own comparison."""

    def __init__(self, config, traffic, devices, seed):
        self.cfg = transformer_config(config)
        self.model = reference_model(config)
        cfg = self.cfg
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        # What the kernel-layer metrics need: one layer's shapes on one
        # chip, and how many layers run them per step, the module's among
        # them. The held rows are the first step's own count
        # (``compare_reference``).
        kinds = cfg.mixer_kinds
        shape = dict(batch=traffic["batch_per_chip"], heads=cfg.n_heads,
                     seq_len=self.seq_len, itemsize=cfg.dtype.itemsize)
        self.kda = dict(shape, k_dim=cfg.kda_head_dim,
                        v_dim=cfg.kda_head_dim, layers=kinds.count("kda"))
        self.ling_mla = dict(
            shape, qk_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, layers=kinds.count("latent_attention"))
        self.moe_share = dict(d=cfg.d_model, d_expert=cfg.d_expert,
                              experts_held=cfg.experts_held,
                              layers=len(kinds) - cfg.num_dense_layers,
                              itemsize=cfg.dtype.itemsize, rows_held=None)
        self.model_flops_per_step = None
        self.moe_held_rows_share = None

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "adamw":
            raise ValueError(f"decoder_ling runner: optimizer {opt_cfg!r}")
        optimizer = optax.adamw(opt_cfg["learning_rate"])
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        # Weights and the batch are made on the device from the seed, each
        # in one jitted call, in the type they are trained in.
        self.params = shard_params(
            jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params),
            cfg, mesh)
        # The balancing biases are no trained parameters: no moments.
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
        self.readings = None
        self._want = None

    def prepare_reference(self):
        """Before the first step (which donates the parameters): what the
        plain float32 reference makes of these weights on the whole
        batch."""
        want = reference_ling.step_readings(self.params, self.tokens,
                                            self.labels, self.model)
        self._want = dict(
            loss=float(want["loss"]), load=np.asarray(want["load"]),
            nll=want["nll"], mtp_nll=want["mtp_nll"],
            bias_before=self.biases())

    def compare_reference(self, first_loss):
        """After the timed executable's first step: its loss, every
        token's two cross-entropies of its forward pass, its own counts
        and the biases it left, each against the reference or the rule."""
        cfg, want = self.cfg, self._want
        load = np.asarray(self.readings["load"])

        def differences(name, want_nll):
            got = jax.device_put(self.readings[name], want_nll.sharding)
            return nll_rms(got, want_nll), nll_median(got, want_nll)

        rms, median = differences("token_nll", want["nll"])
        mtp_rms, mtp_median = differences("mtp_token_nll", want["mtp_nll"])
        # The expert layers' rows, the module's last.
        routed = load[cfg.num_dense_layers:]
        assignments = cfg.moe_top_k * self.samples_per_step
        first = cfg.first_expert_held
        held = routed[:, first:first + cfg.experts_held]
        self.moe_share["rows_held"] = float(held.sum(axis=1).mean())
        self.moe_held_rows_share = float(held.sum() / routed.sum())
        self.model_flops_per_step = self.samples_per_step * \
            model_flops_per_token(
                cfg, self.seq_len,
                self.moe_share["rows_held"] / self.samples_per_step)
        print(f"[bench] tokens per expert, the first step's own counts: "
              f"on held experts {held.sum(axis=1).tolist()} a layer (the "
              f"module's last) of {assignments} assignments (share "
              f"{self.moe_held_rows_share:.5f}; "
              f"{cfg.experts_held / cfg.n_experts:.5f} at balance), windows "
              f"taken {np.asarray(self.readings['windows']).tolist()}, over "
              f"all {cfg.n_experts} experts max "
              f"{routed.max(axis=1).tolist()} min "
              f"{routed.min(axis=1).tolist()}", flush=True)

        err = abs(first_loss - want["loss"]) / abs(want["loss"])
        sums = routed.sum(axis=1)
        moved = int(np.abs(want["load"] - routed).sum()) // 2
        moved_share = moved / float(routed.sum())
        by_rule = np.asarray(reference_ling.updated_bias(
            want["bias_before"], routed, cfg.expert_bias_rate))
        bias_err = float(np.abs(self.biases() - by_rule).max())

        def within(what, got, tol, **more):
            return dict(what=what, got=got, want=0.0, tol=tol,
                        ok=bool(got <= tol), **more)

        return [
            dict(what="first-step loss (main + 0.3 x module) vs float32 "
                      "reference",
                 got=first_loss, want=want["loss"], rel_err=err,
                 tol=LOSS_RTOL,
                 ok=bool(np.isfinite(err) and err <= LOSS_RTOL)),
            within("every token's main cross-entropy of the first step vs "
                   "float32 reference, rms of the difference",
                   rms, NLL_RMS_TOL),
            within("the same, the median of the absolute difference",
                   median, NLL_MEDIAN_TOL),
            within("every token's cross-entropy in the multi-token-"
                   "prediction module vs float32 reference, rms of the "
                   "difference", mtp_rms, MTP_NLL_RMS_TOL),
            within("the module's, the median of the absolute difference",
                   mtp_median, MTP_NLL_MEDIAN_TOL),
            dict(what="tokens per expert of every expert layer and of the "
                      "module's sum to top_k x tokens (nothing dropped), "
                      "a dense layer's to none",
                 got=sums.tolist(), want=assignments, tol=0,
                 ok=bool((sums == assignments).all()
                         and not load[:cfg.num_dense_layers].any())),
            within("assignments the float32 reference routes elsewhere, "
                   "share of all", moved_share, MOVED_SHARE_TOL,
                   moved=moved, of=int(routed.sum())),
            within("every bias after the first step vs the rule on the "
                   "step's own counts, largest difference", bias_err,
                   1e-7)]


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder_ling runner takes token_batches "
                         f"traffic, not {traffic['kind']!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len exceeds the configuration's "
                         "max_position_embeddings")
    return Job(config, traffic, devices, seed)
