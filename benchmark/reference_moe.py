"""Plain float32 reference of the OLMoE decoder (Muennighoff et al.,
arXiv:2409.02060; ``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``):
loss with both router terms, and ``jax.grad`` of it.

Straightforward ``jax.numpy`` at the chip's highest matmul precision, one
sequence at a time, the experts by a plain loop over all of them: no
sort, no grouped matmul, no kernel, no sharding, nothing of
``horovod_tpu`` but its parameter *values* in its layouts.

The model, for one sequence ``x`` [T, d] of embedded tokens and each
layer (no biases anywhere):

1. ``h = RMSNorm(x; g1)`` with ``RMSNorm(v; g) = v * rsqrt(mean(v^2) +
   eps) * g``.
2. ``q = RMSNorm(h Wq; gq)``, ``k = RMSNorm(h Wk; gk)`` over the whole
   projected vector (all heads at once, not per head), ``v = h Wv``;
   split into H heads of Dh; RoPE (rotate-half, base theta) on q and k;
   causal softmax attention scaled by Dh^-1/2; ``x = x + concat(heads)
   Wo``.
3. ``u = RMSNorm(x; g2)``; router logits ``r = u Wr`` [T, E]; ``p =
   softmax(r)``; the k largest ``p`` of a token and their experts
   ``e_j``; weights ``p[e_j]`` as they are (``norm_topk_prob`` false);
   ``y = sum_j p[e_j] * (silu(u Wg[e_j]) * (u Wu[e_j])) Wd[e_j]``;
   ``x = x + y``. Every token reaches all k of its experts.
4. After the last layer ``logits = RMSNorm(x; gf) Wh``, untied.
5. Loss = mean token cross-entropy + ``aux_coef * L_lb + z_coef * L_z``,
   means over layers and sequences of ``L_lb = E * sum_e f_e P_e`` (``f_e``
   = the sequence's tokens with ``e`` among their k, over T; ``P_e`` = mean
   of ``p[:, e]``; k at perfect balance) and ``L_z = mean_t
   logsumexp(r_t)^2``.

Departures from the published model are listed in
``configs/olmoe-1b-7b.json`` under ``assumed``.

Layouts (``models/transformer.py``'s; the two leading axes [stages,
layers a stage] are read as one axis of layers): ``embed`` [V, d];
``ln1``, ``ln2`` [S, L, d]; ``wqkv`` [S, L, d, 3, H, Dh]; ``gq``, ``gk``
[S, L, H, Dh]; ``wo`` [S, L, H, Dh, d]; ``router`` [S, L, d, E]; ``wg``,
``wu`` [S, L, E, d, f]; ``wd`` [S, L, E, f, d]; ``final_ln`` [d];
``head`` [d, V].

Parameters are cast to float32 where they are used, an expert at a time,
and each layer and each expert is under ``jax.checkpoint``: that changes
what the backward pass keeps, not what is computed, and lets the
gradient at published widths fit on a chip beside float32 parameters.
"""

import jax
import jax.numpy as jnp
from jax import lax

_LAYER_KEYS = ("ln1", "ln2", "wqkv", "gq", "gk", "wo", "router", "wg",
               "wu", "wd")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * _f32(g)


def _rope(x, theta):
    """Rotate-half rotary embedding of x [T, H, Dh] at positions 0..T-1."""
    T, _, Dh = x.shape
    half = Dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(u, weights, lp):
    """``sum_e weights[:, e] * expert_e(u)``, every expert on every
    token; ``weights`` [T, E] is zero where the token did not pick e."""
    @jax.checkpoint
    def one(y, args):
        w, wg, wu, wd = args
        hidden = jax.nn.silu(u @ _f32(wg)) * (u @ _f32(wu))
        return y + w[:, None] * (hidden @ _f32(wd)), None

    y, _ = lax.scan(one, jnp.zeros_like(u),
                    (weights.T, lp["wg"], lp["wu"], lp["wd"]))
    return y


def sequence_forward(params, tokens, top_k, eps=1e-5, theta=10000.0):
    """Logits [T, V] of one sequence ``tokens`` [T], and per layer the
    load-balance term, the z term and the tokens per expert [L, E]."""
    layers = {k: params[k].reshape((-1,) + params[k].shape[2:])
              for k in _LAYER_KEYS}
    T = tokens.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    @jax.checkpoint
    def layer(x, lp):
        d = x.shape[-1]
        h = _rmsnorm(x, lp["ln1"], eps)
        wqkv = _f32(lp["wqkv"])
        H, Dh = wqkv.shape[-2:]
        q, k, v = (h @ wqkv[:, c].reshape(d, H * Dh) for c in range(3))
        q = _rmsnorm(q, lp["gq"].reshape(-1), eps).reshape(T, H, Dh)
        k = _rmsnorm(k, lp["gk"].reshape(-1), eps).reshape(T, H, Dh)
        q, k, v = _rope(q, theta), _rope(k, theta), v.reshape(T, H, Dh)
        s = jnp.einsum("thk,shk->hts", q, k) * Dh ** -0.5
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hts,shk->thk", a, v)
        x = x + o.reshape(T, H * Dh) @ _f32(lp["wo"]).reshape(H * Dh, d)

        u = _rmsnorm(x, lp["ln2"], eps)
        r = u @ _f32(lp["router"])
        p = jax.nn.softmax(r, -1)
        E = p.shape[-1]
        _, chosen = lax.top_k(p, top_k)
        picked = jnp.any(chosen[..., None] == jnp.arange(E), axis=1)
        x = x + _experts(u, jnp.where(picked, p, 0.0), lp)
        f = jnp.mean(picked.astype(jnp.float32), axis=0)
        lb = E * jnp.sum(f * jnp.mean(p, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(r, -1)))
        return x, (lb, z, jnp.sum(picked, axis=0))

    x = _f32(params["embed"][tokens])
    x, (lb, z, load) = lax.scan(layer, x, layers)
    logits = _rmsnorm(x, params["final_ln"], eps) @ _f32(params["head"])
    return logits, lb, z, load


def decoder_moe_loss(params, tokens, labels, top_k, aux_coef, z_coef,
                     eps=1e-5, theta=10000.0):
    """The training loss over ``tokens`` [B, T] (equation 5 above), and
    the tokens per expert [L, E] summed over the batch."""
    def one_sequence(args):
        toks, labs = args
        logits, lb, z, load = sequence_forward(params, toks, top_k, eps,
                                               theta)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, labs[:, None], -1)[:, 0]
        return jnp.mean(nll), jnp.mean(lb), jnp.mean(z), load

    with jax.default_matmul_precision("highest"):
        # One sequence at a time: the float32 logits and attention
        # scores of a whole batch would not fit beside the program.
        nll, lb, z, load = lax.map(one_sequence, (tokens, labels))
    loss = jnp.mean(nll) + aux_coef * jnp.mean(lb) + z_coef * jnp.mean(z)
    return loss, jnp.sum(load, axis=0)


def decoder_moe_loss_and_grad(params, tokens, labels, top_k, aux_coef,
                              z_coef, eps=1e-5, theta=10000.0):
    """((loss, tokens per expert), gradient of the loss by every
    parameter leaf, in the leaf's own dtype)."""
    return jax.value_and_grad(decoder_moe_loss, has_aux=True)(
        params, tokens, labels, top_k, aux_coef, z_coef, eps, theta)
