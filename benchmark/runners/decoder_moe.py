"""Runner ``decoder_moe``: ``models/transformer.py``'s decoder with
RMSNorm, QK-norm, RoPE and the dropless expert layer
(``parallel/moe.py``) through ``transformer.make_train_step`` on
``build_parallel_mesh`` (dp over the cell's chips, every chip holding
all experts' share of the dp axis), the program's own initialiser and
optimizer-state helper. Reads a configuration with OLMoE's published
keys (configs/olmoe-1b-7b.json) and a ``token_batches`` traffic file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_router_load_fn, make_train_step,
    shard_params)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops_moe, reference_moe

# System (bf16 parameters, activations and expert matmuls; float32
# norms, router, head and loss) against the float32 reference, relative,
# on the loss of the first step over the whole batch, router terms
# included. Read on the chip: 1.7e-6 to 4.2e-5 over nine runs of the
# cell (PERF.md, PR 26). Two things make it: the chip's float32 log,
# 1e-4 coarse in absolute terms on a loss near 11.5 as in the dense
# decoder, and the 223 to 296 of 131,072 assignments (0.2 %) that the
# float32 reference routes to another expert than the program, whose
# router reads bf16 activations: each moves one token's output a little
# and the load-balance term by 1e-6 of the loss. The tolerance is five
# times the largest reading. A head, a router or an expert layer in a
# lower precision than stated, a wrong norm, scale, rotation or gate
# weighting, or a dropped token is off by 1e-3 and more.
LOSS_RTOL = 2e-4


def transformer_config(config):
    """The program's ``TransformerConfig`` of a configuration file with
    OLMoE's published keys."""
    heads = config["num_attention_heads"]
    if config["num_key_value_heads"] != heads:
        raise ValueError("decoder_moe runner: grouped-query attention is "
                         "not what this configuration publishes")
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("decoder_moe runner: gated SiLU experts and an "
                         "untied head are what the program builds")
    assumed = config["assumed"]
    d = config["hidden_size"]
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=d, n_heads=heads,
        d_head=d // heads, n_layers=config["num_hidden_layers"],
        max_seq=config["max_position_embeddings"], use_moe=True,
        n_experts=config["num_experts"],
        d_expert=config["intermediate_size"],
        moe_top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_loss_coef=assumed["router_aux_loss_coef"],
        router_z_loss_coef=assumed["router_z_loss_coef"],
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], qk_norm=True,
        rope=True, rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(config["dtype"]))


class Job:
    sample_unit = "tokens"

    def __init__(self, config, traffic, devices, seed):
        self.cfg = transformer_config(config)
        cfg = self.cfg
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        sizes = dict(d=cfg.d_model, d_expert=cfg.d_expert,
                     experts_per_token=cfg.moe_top_k)
        self.model_flops_per_step = self.samples_per_step * \
            flops_moe.decoder_moe_train_flops_per_token(
                layers=cfg.n_layers, n_experts=cfg.n_experts,
                vocab_rows=cfg.vocab, seq_len=self.seq_len, **sizes)
        # What the kernel-layer metrics need: one layer's shapes on one
        # chip, and how many layers run them per step.
        self.attention = dict(batch=traffic["batch_per_chip"],
                              heads=cfg.n_heads,
                              seq_len=self.seq_len, head_dim=cfg.d_head,
                              layers=cfg.n_layers,
                              itemsize=cfg.dtype.itemsize)
        self.moe = dict(tokens=traffic["batch_per_chip"] * self.seq_len,
                        n_experts=cfg.n_experts, layers=cfg.n_layers,
                        itemsize=cfg.dtype.itemsize, **sizes)
        self.moe_load_max_over_mean = None

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "adamw":
            raise ValueError(f"decoder_moe runner: optimizer {opt_cfg!r}")
        optimizer = optax.adamw(opt_cfg["learning_rate"])
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        # Weights and the batch are made on the device from the seed, each
        # in one jitted call, in the type they are trained in.
        self.params = shard_params(
            jax.jit(lambda k: init_params(cfg, k, n_stages=1))(k_params),
            cfg, mesh)
        self.opt_state = init_opt_state(optimizer, self.params, mesh)
        data = NamedSharding(mesh, P("dp", "sp"))
        vocab, shape = config["vocab_size"], (self.batch, self.seq_len)

        def make_batch(k):
            tokens = jax.random.randint(k, shape, 0, vocab, jnp.int32)
            return tokens, jnp.roll(tokens, -1, axis=1)

        self.tokens, self.labels = jax.jit(
            make_batch, out_shardings=(data, data))(k_tokens)
        self.step_fn = make_train_step(cfg, optimizer, mesh,
                                       n_microbatches=1)
        self.load_fn = make_router_load_fn(cfg, mesh, n_microbatches=1)
        self.compiled = None
        self._ref_loss = None

    def lower(self):
        return self.step_fn.lower(self.params, self.opt_state, self.tokens,
                                  self.labels)

    def step(self):
        self.params, self.opt_state, loss = self.compiled(
            self.params, self.opt_state, self.tokens, self.labels)
        return loss

    def prepare_reference(self):
        """Before the first step (which donates the parameters): where
        the router sends this batch, by the program's own count and by
        the reference's, and the plain float32 loss of these weights on
        the whole batch."""
        cfg = self.cfg
        load = np.asarray(self.load_fn(self.params, self.tokens))
        want = cfg.moe_top_k * self.samples_per_step
        if not (load.sum(axis=1) == want).all():
            raise RuntimeError(
                f"tokens per expert sum to {load.sum(axis=1).tolist()} a "
                f"layer, not {cfg.moe_top_k} x {self.samples_per_step}: "
                f"tokens were dropped")
        per_layer = load.max(axis=1) / load.mean(axis=1)
        self.moe_load_max_over_mean = float(per_layer.max())
        print(f"[bench] tokens per expert, max / mean by layer "
              f"{[round(float(x), 4) for x in per_layer]} (max "
              f"{load.max(axis=1).tolist()}, min "
              f"{load.min(axis=1).tolist()}); every layer's sum is "
              f"{cfg.moe_top_k} x {self.samples_per_step}", flush=True)

        one = self.tokens.sharding.mesh.devices.flat[0]
        put = lambda x: jax.device_put(x, one)
        ref = jax.jit(lambda p, t, l: reference_moe.decoder_moe_loss(
            p, t, l, cfg.moe_top_k, cfg.router_aux_loss_coef,
            cfg.router_z_loss_coef, cfg.norm_eps, cfg.rope_theta))
        ref_loss, ref_load = ref(
            jax.tree_util.tree_map(put, self.params), put(self.tokens),
            put(self.labels))
        self._ref_loss = float(ref_loss)
        moved = int(np.abs(np.asarray(ref_load) - load).sum()) // 2
        print(f"[bench] assignments the float32 reference routes "
              f"elsewhere: {moved} of {want * cfg.n_layers}", flush=True)

    def compare_reference(self, first_loss):
        err = abs(first_loss - self._ref_loss) / abs(self._ref_loss)
        return [dict(what="first-step loss (cross-entropy + router terms) "
                          "vs float32 reference",
                     got=first_loss, want=self._ref_loss, rel_err=err,
                     tol=LOSS_RTOL, ok=bool(np.isfinite(err)
                                            and err <= LOSS_RTOL))]

    def close(self):
        pass


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder_moe runner takes token_batches "
                         f"traffic, not {traffic['kind']!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len exceeds the configuration's "
                         "max_position_embeddings")
    return Job(config, traffic, devices, seed)
