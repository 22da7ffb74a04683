"""Plain references the measured steps are held to.

Straightforward ``jax.numpy`` written from the published descriptions,
with no kernel, no sharding, no ``horovod_tpu`` and no flax in it. They
take the program's parameter *values* (seeded weights have to come from
somewhere) in the layouts noted below, and nothing else of the program.
The runners call them outside the timed window.
"""

import jax
import jax.numpy as jnp
import optax
from jax import lax


# ---- GPT-2-style decoder, float32 -----------------------------------------

def _layernorm(x, scale, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale


def decoder_loss(params, tokens, labels, eps=1e-5):
    """Mean next-token cross-entropy of the decoder over ``tokens``
    [B, T], everything in float32 at the chip's highest matmul precision
    (a float32 matmul otherwise runs in bf16 on a TPU).

    Layouts (``models/transformer.py``'s, one pipeline stage): ``embed``
    [V, d], ``pos`` [T_max, d], per layer stacked on axes [1, L]: ``ln1``,
    ``ln2`` [d], ``wqkv`` [d, 3, H, Dh], ``wo`` [H, Dh, d], ``w1``
    [d, F], ``w2`` [F, d]; ``final_ln`` [d], ``head`` [d, V]. Departures
    from GPT-2 as published are the program's and are listed in
    configs/gpt2s.json: untied head, no biases, scale-only LayerNorm.
    """
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    layers = {k: p[k][0] for k in ("ln1", "ln2", "wqkv", "wo", "w1", "w2")}
    T = tokens.shape[1]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def one_sequence(args):
        toks, labs = args
        x = p["embed"][toks] + p["pos"][:T]

        def layer(x, lp):
            h = _layernorm(x, lp["ln1"], eps)
            qkv = jnp.einsum("td,dchk->cthk", h, lp["wqkv"])
            q, k, v = qkv[0], qkv[1], qkv[2]
            s = jnp.einsum("thk,shk->hts", q, k) / jnp.sqrt(
                jnp.float32(q.shape[-1]))
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            o = jnp.einsum("hts,shk->thk", a, v)
            x = x + jnp.einsum("thk,hkd->td", o, lp["wo"])
            h = _layernorm(x, lp["ln2"], eps)
            # gelu_new: the tanh approximation, GPT-2's activation.
            y = jax.nn.gelu(h @ lp["w1"], approximate=True) @ lp["w2"]
            return x + y, None

        x, _ = lax.scan(layer, x, layers)
        logits = _layernorm(x, p["final_ln"], eps) @ p["head"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, labs[:, None], -1)[:, 0]

    with jax.default_matmul_precision("highest"):
        # One sequence at a time: float32 logits of a whole batch would
        # not fit beside the program's own state.
        nll = lax.map(one_sequence, (tokens, labels))
    return jnp.mean(nll)


# ---- ResNet-50 (He et al. 2015, Table 1; v1.5 stride placement) ------------

def _conv(x, kernel, stride, dtype):
    return lax.conv_general_dilated(
        x.astype(dtype), kernel.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, dtype, eps=1e-5):
    """Training-mode batch norm: this batch's statistics, in float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, (0, 1, 2))
    var = jnp.mean(jnp.square(xf - mean), (0, 1, 2))
    y = (xf - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dtype)


def resnet50_logits(params, images, stage_sizes, dtype):
    """ResNet-50 forward in training mode on ``images`` [N, H, W, 3].

    ``params`` is the flax tree of ``models/resnet.py`` (``conv_init``,
    ``bn_init``, ``BottleneckBlock_<i>`` with ``Conv_0..2``,
    ``BatchNorm_0..2`` and, where the shape changes, ``conv_proj`` /
    ``norm_proj``; ``Dense_0``). Mixed precision as configs/resnet50.json
    states it: convolution operands and activations in ``dtype``,
    statistics, head and loss in float32 — a float32 copy of a batch of
    256 does not fit the chip beside the program under test, and
    batch-norm statistics need the whole batch at once.
    """
    x = _conv(images, params["conv_init"]["kernel"], 2, dtype)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], dtype))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    block = 0
    for stage, count in enumerate(stage_sizes):
        for j in range(count):
            bp = params[f"BottleneckBlock_{block}"]
            block += 1
            stride = 2 if stage > 0 and j == 0 else 1
            y = _conv(x, bp["Conv_0"]["kernel"], 1, dtype)
            y = jax.nn.relu(_batch_norm(y, bp["BatchNorm_0"], dtype))
            y = _conv(y, bp["Conv_1"]["kernel"], stride, dtype)
            y = jax.nn.relu(_batch_norm(y, bp["BatchNorm_1"], dtype))
            y = _conv(y, bp["Conv_2"]["kernel"], 1, dtype)
            y = _batch_norm(y, bp["BatchNorm_2"], dtype)
            if "conv_proj" in bp:
                x = _conv(x, bp["conv_proj"]["kernel"], stride, dtype)
                x = _batch_norm(x, bp["norm_proj"], dtype)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x.astype(jnp.float32), (1, 2))
    with jax.default_matmul_precision("highest"):
        return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


def resnet50_sgd_step(params, images, labels, replicas, stage_sizes, dtype,
                      learning_rate, momentum):
    """One plain SGD-with-momentum step from a zero momentum buffer, on
    one device: ``jax.grad`` of the mean cross-entropy, ``optax.sgd``.

    ``replicas`` > 1 stands for data-parallel training with per-replica
    batch-norm statistics: the batch is cut into that many contiguous
    shards, each normalised by its own statistics, and the loss is the
    mean over shards (one shard after another, so one shard's
    activations are alive at a time). Returns (loss, new params).
    """
    def loss_fn(p, x, y):
        logits = resnet50_logits(p, x, stage_sizes, dtype)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

    shard = (replicas, images.shape[0] // replicas)
    xs = images.reshape(shard + images.shape[1:])
    ys = labels.reshape(shard)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def one_shard(carry, xy):
        loss, grads = jax.value_and_grad(loss_fn)(params, *xy)
        return (carry[0] + loss / replicas,
                jax.tree_util.tree_map(lambda a, g: a + g / replicas,
                                       carry[1], grads)), None

    (loss, grads), _ = lax.scan(one_shard, (jnp.float32(0), zeros), (xs, ys))
    opt = optax.sgd(learning_rate, momentum=momentum)
    updates, _ = opt.update(grads, opt.init(params), params)
    return loss, optax.apply_updates(params, updates)
