"""Runner ``decoder_hybrid``: ``models/transformer.py``'s decoder with a
layer pattern of Mamba-2 and attention mixers, RMSNorm, a gated SiLU MLP,
a tied head and Granite's multipliers, through
``transformer.make_train_step`` on ``build_parallel_mesh`` (dp over the
cell's chips), the program's own initialiser and optimizer-state helper.
Reads a configuration with ``granitemoehybrid``'s published keys
(configs/granite-4.0-h-micro.json) and a ``token_batches`` traffic
file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_forward_fn, make_train_step,
    shard_params, token_nll)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops_ssd, reference_hybrid

# System (bf16 parameters, activations and matmul operands; float32
# norms, dt, decays, chunk states, head and loss) against the float32
# reference (the state-space layers as a recurrence over the 8,192
# tokens), on the first step's weights and batch, twice.
#
# (1) The loss of the first training step, relative. Read on the chip:
# 8.3e-8 to 4.1e-6 over the cell's first twenty-five runs (PERF.md
# section 6, PR 30). The per-token reading under (2) implies a standard
# deviation of 1.73e-3 / sqrt(8192) / 11.52 = 1.7e-6 for it; the
# tolerance is six of them. It holds the *step* to the reference (the
# loss the optimizer sees is the forward's) and catches what is wrong in
# the mathematics (tests/test_hybrid.py holds a wrong multiplier, a
# missing D or gate, a cumulative sum off by one token and a dropped
# carried state at a tiny size). It is no limit on precision: at
# initialisation the loss sits within 0.01 of ln 100,352 and bf16's
# roundings average out over 8,192 tokens, so the reference itself with
# bf16 matmul operands reads 4e-7 to 1.2e-6, and the program with its
# cumulative sums of ``dt A`` in bf16 2.3e-5 to 6.7e-5, too near.
LOSS_RTOL = 1e-5

# (2) Every token's cross-entropy from the program's forward pass
# (``make_forward_fn`` over the same stage function, then the program's
# ``token_nll``), as the root of the mean squared difference from the
# reference's over the 8,192 tokens. This one sees precision: a token's
# cross-entropy averages nothing out. Read on the chip by ``python3 -m
# benchmark.limit_check_hybrid`` and by the cell's own runs (PERF.md
# section 6, PR 30, has every reading): as stated 1.70e-3 to 1.77e-3
# over sixteen readings, all of it the bf16 rounding of activations that
# the configuration states; with both cumulative sums of ``dt A`` in
# bf16 (one float32 part in the precision below) 3.49e-2 to 4.10e-2 at
# five seeds, twenty times that. The limit is 2.0e-3, 13 % over the
# largest sound reading. What it cannot refuse, and why: ``dt``, the
# chunk states and their carry, the gated norm or the logits in bf16
# read 1.70e-3 to 1.78e-3, inside or within 1 % of the sound readings,
# and the block norms 1.83e-3 to 1.87e-3, 4 to 8 % over them: the
# residual stream and every activation around those parts are bf16
# already and round as coarsely (a v5e has no bf16 vector unit and XLA
# keeps a fusion's intermediates in float32, so "in bf16" there is one
# more rounding where a value is written). A limit at 1.80e-3 would
# refuse the block norms too and leave a sound run 1.8 % of room; the
# driver draws new seeds for every check, so it was not taken.
NLL_RMS_TOL = 2e-3

# The configuration's ``recompute`` (``assumed.recompute`` says how it
# was chosen) as ``TransformerConfig.remat``.
_REMAT = {"none": False, "layers": True}


def layer_types(config):
    """The layers this configuration runs: the first
    ``num_hidden_layers`` of the published pattern."""
    return tuple(config["layer_types"][:config["num_hidden_layers"]])


def transformer_config(config):
    """The program's ``TransformerConfig`` of a configuration file with
    ``granitemoehybrid``'s published keys."""
    if (config["num_local_experts"] or config["hidden_act"] != "silu"
            or config["position_embedding_type"] != "nope"
            or config["normalization_function"] != "rmsnorm"
            or config["mamba_n_groups"] != 1 or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]):
        raise ValueError(
            "decoder_hybrid runner: no experts, gated SiLU, no positions, "
            "RMSNorm, one group of B and C, a bias on the convolution "
            "alone are what the program builds")
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["mamba_n_heads"] * config["mamba_d_head"] != \
            config["mamba_expand"] * d:
        raise ValueError("decoder_hybrid runner: mamba heads x head width "
                         "is not mamba_expand x hidden_size")
    return TransformerConfig(
        vocab=config["vocab_size"], d_model=d, n_heads=heads,
        d_head=d // heads, n_kv_heads=config["num_key_value_heads"],
        d_ff=config["shared_intermediate_size"],
        n_layers=config["num_hidden_layers"],
        max_seq=config["max_position_embeddings"],
        layer_types=layer_types(config),
        mamba_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], gated_mlp=True,
        tie_embeddings=config["tie_word_embeddings"], pos_table=False,
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        attention_multiplier=float(config["attention_multiplier"]),
        remat=_REMAT[config["recompute"]],
        dtype=jnp.dtype(config["dtype"]))


def reference_model(config):
    """What ``reference_hybrid`` needs of the configuration."""
    return dict(layer_types=layer_types(config),
                **{k: config[k] for k in (
                    "rms_norm_eps", "embedding_multiplier",
                    "residual_multiplier", "attention_multiplier",
                    "logits_scaling")})


def program_token_nll(cfg, mesh):
    """nll(params, tokens, labels) [B, T]: every token's cross-entropy by
    the program's forward pass and its own ``token_nll``."""
    forward = make_forward_fn(cfg, mesh, n_microbatches=1)
    return jax.jit(lambda params, tokens, labels: token_nll(
        forward(params, tokens), labels))


def nll_rms(got, want):
    """Root of the mean squared difference of two [B, T] cross-entropies."""
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want))))


class Job:
    sample_unit = "tokens"

    def __init__(self, config, traffic, devices, seed):
        self.cfg = transformer_config(config)
        self.model = reference_model(config)
        cfg = self.cfg
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        self.model_flops_per_step = self.samples_per_step * \
            flops_ssd.hybrid_train_flops_per_token(
                d=cfg.d_model, d_ff=cfg.d_ff, layer_types=cfg.kinds,
                n_heads=cfg.n_heads, n_kv_heads=cfg.kv_heads,
                head_dim=cfg.d_head, mamba_heads=cfg.mamba_heads,
                mamba_d_head=cfg.mamba_d_head,
                mamba_d_state=cfg.mamba_d_state,
                mamba_chunk=cfg.mamba_chunk, vocab_rows=cfg.vocab,
                seq_len=self.seq_len)
        self.gated_mlp = cfg.gated_mlp  # whose ``mlp`` scope it is
        # What ``ssd_roofline`` needs: one Mamba layer's scan on one
        # chip, and how many layers run it per step.
        self.ssd = dict(tokens=traffic["batch_per_chip"] * self.seq_len,
                        chunk=cfg.mamba_chunk, d_state=cfg.mamba_d_state,
                        groups=1, d_head=cfg.mamba_d_head,
                        heads=cfg.mamba_heads,
                        layers=cfg.kinds.count("mamba"),
                        itemsize=cfg.dtype.itemsize)

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "adamw":
            raise ValueError(f"decoder_hybrid runner: optimizer {opt_cfg!r}")
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
        """Before the first step (which donates the parameters): every
        token's cross-entropy of these weights on the whole batch, by
        the plain float32 reference (the state-space layers as a
        recurrence over the tokens) and by the program's forward pass."""
        mesh = self.tokens.sharding.mesh
        one = mesh.devices.flat[0]
        put = lambda x: jax.device_put(x, one)
        model = self.model
        ref = jax.jit(lambda p, t, l: reference_hybrid.token_nll(
            p, t, l, model))
        want = ref(jax.tree_util.tree_map(put, self.params),
                   put(self.tokens), put(self.labels))
        self._ref_loss = float(jnp.mean(want))
        self._nll_rms = nll_rms(
            put(program_token_nll(self.cfg, mesh)(
                self.params, self.tokens, self.labels)), want)

    def compare_reference(self, first_loss):
        err = abs(first_loss - self._ref_loss) / abs(self._ref_loss)
        return [dict(what="first-step loss vs float32 reference (the scan "
                          "as a recurrence)",
                     got=first_loss, want=self._ref_loss, rel_err=err,
                     tol=LOSS_RTOL, ok=bool(np.isfinite(err)
                                            and err <= LOSS_RTOL)),
                dict(what="every token's cross-entropy vs float32 "
                          "reference, rms of the difference",
                     got=self._nll_rms, want=0.0, tol=NLL_RMS_TOL,
                     ok=bool(self._nll_rms <= NLL_RMS_TOL))]

    def close(self):
        pass


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder_hybrid runner takes token_batches "
                         f"traffic, not {traffic['kind']!r}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len exceeds the configuration's "
                         "max_position_embeddings")
    return Job(config, traffic, devices, seed)
