"""Runner ``decoder``: ``models/transformer.py``'s dense decoder through
``transformer.make_train_step`` on ``build_parallel_mesh`` (dp over the
cell's chips), the program's own initialiser and optimizer-state helper.
Reads a configuration with GPT-2's published keys (configs/gpt2s.json)
and a ``token_batches`` traffic file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_train_step, shard_params)
from horovod_tpu.parallel.mesh import build_parallel_mesh
from horovod_tpu.training import init_opt_state

from benchmark import flops, reference

# System (bf16 parameters and activations, float32 head and loss) against
# the float32 reference, relative, on the loss of the first step over
# the whole batch. Read on the chip: 1.4e-6 to 2.0e-5 over 28 runs of the
# two cells (PERF.md, PR 23), which is what the chip's float32 log leaves
# (1e-4 coarse in absolute terms on a loss near 11.3); bf16's rounding
# of the activations averages out over 8,192 tokens. The tolerance is
# five times the largest reading. A wrong mask, scale, activation or
# position table is off by 1e-2 and more.
LOSS_RTOL = 1e-4


class Job:
    sample_unit = "tokens"

    def __init__(self, config, traffic, devices, seed):
        assumed = config["assumed"]
        self.cfg = TransformerConfig(
            vocab=assumed["padded_vocab_rows"], d_model=config["n_embd"],
            n_heads=config["n_head"], d_head=assumed["head_dim"],
            d_ff=assumed["d_ff"], n_layers=config["n_layer"],
            max_seq=config["n_positions"], dtype=jnp.dtype(config["dtype"]))
        self.eps = config["layer_norm_epsilon"]
        self.seq_len = traffic["seq_len"]
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.samples_per_step = self.batch * self.seq_len
        self.model_flops_per_step = self.samples_per_step * \
            flops.decoder_train_flops_per_token(
                n_embd=config["n_embd"], n_layer=config["n_layer"],
                d_ff=assumed["d_ff"],
                vocab_rows=assumed["padded_vocab_rows"],
                seq_len=self.seq_len)
        # What the kernel-layer metrics need: one layer's attention shape
        # on one chip, and how many layers run it per step.
        self.attention = dict(batch=traffic["batch_per_chip"],
                              heads=config["n_head"], seq_len=self.seq_len,
                              head_dim=assumed["head_dim"],
                              layers=config["n_layer"],
                              itemsize=self.cfg.dtype.itemsize)

        mesh = build_parallel_mesh(devices, sp=1, tp=1, pp=1)
        opt_cfg = config["optimizer"]
        if opt_cfg["name"] != "adamw":
            raise ValueError(f"decoder runner: optimizer {opt_cfg!r}")
        optimizer = optax.adamw(opt_cfg["learning_rate"])
        k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        # Weights and the batch are made on the device from the seed, each
        # in one jitted call, in the type they are trained in.
        cfg = self.cfg
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
        """Before the first step (which donates the parameters): the
        plain float32 loss of these weights on the whole batch."""
        one = self.tokens.sharding.mesh.devices.flat[0]
        put = lambda x: jax.device_put(x, one)
        eps = self.eps
        ref = jax.jit(lambda p, t, l: reference.decoder_loss(p, t, l, eps))
        self._ref_loss = float(ref(
            jax.tree_util.tree_map(put, self.params), put(self.tokens),
            put(self.labels)))

    def compare_reference(self, first_loss):
        err = abs(first_loss - self._ref_loss) / abs(self._ref_loss)
        return [dict(what="first-step loss vs float32 reference",
                     got=first_loss, want=self._ref_loss, rel_err=err,
                     tol=LOSS_RTOL, ok=bool(np.isfinite(err)
                                            and err <= LOSS_RTOL))]

    def close(self):
        pass


def build(config, traffic, devices, seed):
    if traffic["kind"] != "token_batches":
        raise ValueError("the decoder runner takes token_batches traffic, "
                         f"not {traffic['kind']!r}")
    if traffic["seq_len"] > config["n_positions"]:
        raise ValueError("seq_len exceeds the configuration's n_positions")
    return Job(config, traffic, devices, seed)
