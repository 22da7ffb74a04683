"""Plain float32 reference of the ZAYA1-8B decoder (``model_type``
``zaya``: ``Zyphra/ZAYA1-8B`` ``config.json``; compressed convolutional
attention, arXiv:2510.04476 section 3; the model, its router and its
residual scaling, arXiv:2511.17127), as one chip of a deployment holds it:
the loss, every token's cross-entropy, the tokens each expert got in each
layer, the router's balancing bias after one application of its rule, and
``jax.grad`` of the loss by every trained leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision: no
kernel, no sort, no scan over layers or experts, no sharding, nothing of
``horovod_tpu`` but its parameter *values* in its layouts. Attention is a
dense causal mask, formed a block of queries at a time so that ``[H, T,
T]`` scores never exist whole; every held expert is applied to every token
under a ``0 / p`` mask; the logits are formed a block of tokens at a time
(memory forces that at the timed size: 16,384 x 131,136 float32 are 8.6
GB).

One layer ``l`` on the residual stream ``x`` [T, d], with ``rms(v; g) = v *
rsqrt(mean(v^2) + eps) * g`` (eps 1e-5), ``d`` 2,048, ``Hq`` 8 query heads
over ``Hkv`` 2 key/value heads of ``Dh`` 128, group ``g = Hq / Hkv``, ``E``
16 experts, ``R`` 256, no biases; float32 ``a``, ``b``, ``c`` [d] per block:

    x = a_1 * x + b_1 + c_1 * CCA(rms(x; g_1))
    x = a_2 * x + b_2 + c_2 * MoE(rms(x; g_2), r_{l-1})        -> also r_l

CCA on normed ``h``:

1. ``q~ = h W_q`` [T, Hq, Dh]; ``k~ = h W_k`` [T, Hkv, Dh].
2. ``v = h W_v`` [T, Hkv, Dh], and the upper half of the value heads (head
   1 of 2) reads the token before: ``v_t[j] = (h_{t-1} W_v)[j]`` for ``j
   >= Hkv / 2``, zero at ``t = 0``.
3. conv0, causal and depthwise, width ``K0`` = 2: ``y_t[c] = sum_j w0[j, c]
   x_{t - (K0 - 1) + j}[c]``; conv1, causal and by head, width ``K1`` = 2:
   ``y_t[i] = sum_j x_{t - (K1 - 1) + j}[i] W1[j, i]``, ``W1[j, i]`` [Dh,
   Dh]; zeros before the first token. ``q^ = conv1(conv0(q~))``, ``k^ =
   conv1(conv0(k~))``, filters of their own.
4. ``q = q^ + (q~ + repeat_g(k~)) / 2``; ``k = k^ + (mean_g(q~) + k~) /
   2``: query head ``i`` reads key head ``i // g``, key head ``j`` the
   mean of query heads ``j g .. (j + 1) g``.
5. ``q = q * rsqrt(mean(q^2) + eps)`` (that is ``sqrt(Dh) q / |q|``), ``k =
   beta_j k * rsqrt(mean(k^2) + eps)`` per head.
6. The first ``rotated`` (64) of a head's channels of ``q`` and ``k`` are
   rotated at the token's position (rotate-half: channel ``c`` pairs with
   ``c + rotated / 2``; base theta, no scaling).
7. ``o_i = softmax_{s <= t}(q_i . k_{i // g, s} / sqrt(Dh)) v_{i // g}``;
   ``CCA = concat_i(o_i) W_o``.

The expert block on normed ``u``:

8. ``r_l = u W_down + gamma_l r_{l-1}`` [T, R], ``r_{-1} = 0``.
9. ``s = gelu(gelu(rms(r_l; g_r) W_1) W_2) W_3`` [T, E]; ``p =
   softmax(s)``; ``e = argmax(p + bias_l)`` (the bias: indices only, no
   gradient); ``MoE = p_e expert_e(u)`` if ``e`` is held, else nothing:
   the chip's share of the layer. Experts are gated SiLU MLPs.
10. Loss: ``CE(rms(x^L; g_f) Emb^T, t_{i+1})`` through the tied table,
    mean over all tokens, over the rows held here, no auxiliary term.
11. After the step ``c_l`` = tokens per expert of layer ``l`` [E], ``delta
    = rate * sign(mean(c_l) - c_l)``, ``bias_l += delta - mean(delta)``.

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
a stage] are read as one axis): ``embed`` [V, d]; ``ln1``, ``ln2`` [S, L,
d]; ``res1``, ``res2`` [S, L, 3, d] (a, b, c); ``c_wq`` [S, L, d, Hq, Dh],
``c_wk``, ``c_wv`` [S, L, d, Hkv, Dh]; ``c_conv0_q`` [S, L, K0, Hq, Dh],
``c_conv0_k`` [S, L, K0, Hkv, Dh]; ``c_conv1_q`` [S, L, K1, Hq, Dh, Dh],
``c_conv1_k`` [S, L, K1, Hkv, Dh, Dh] (in, out); ``c_beta`` [S, L, Hkv];
``c_wo`` [S, L, Hq, Dh, d]; ``r_down`` [S, L, d, R], ``r_gamma`` [S, L, 1],
``r_norm`` [S, L, R], ``r_w1``, ``r_w2`` [S, L, R, R], ``r_w3`` [S, L, R,
E]; ``wg``, ``wu`` [S, L, E_held, d, f], ``wd`` [S, L, E_held, f, d];
``expert_bias`` [S, L, E]; ``final_ln`` [d].

``model`` is a dict: ``num_hidden_layers``, ``rms_norm_eps``,
``rope_theta``, ``rotated`` (channels of a head that are rotated),
``first_expert_held`` (the held experts are that one and the following, as
many as ``wg`` holds) and ``load_balance_coeff``. Each layer, the head and each block of
512 queries or logit rows is under ``jax.checkpoint``: that changes what the backward pass keeps, not what
is computed.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_ROWS = 512  # queries or logit rows formed at once
_LEAVES = ("ln1", "ln2", "res1", "res2", "c_wq", "c_wk", "c_wv",
           "c_conv0_q", "c_conv0_k", "c_conv1_q", "c_conv1_k", "c_beta",
           "c_wo", "r_down", "r_gamma", "r_norm", "r_w1", "r_w2", "r_w3",
           "wg", "wu", "wd", "expert_bias")
_BIASES = ("expert_bias",)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * _f32(g)


def _blocks(T):
    size = _ROWS if T % _ROWS == 0 else T
    return [(at, size) for at in range(0, T, size)]


def rotate(x, theta):
    """Rotate-half rotary embedding of x [B, T, H, n] over all n of its
    last channels at positions 0..T-1."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def shifted(x, by):
    """``x_{t - by}`` along axis 1, zeros before the first token."""
    if by == 0:
        return x
    return jnp.pad(x, [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2))[
        :, :x.shape[1]]


def conv_depthwise(x, w):
    """Equation 3's conv0 on x [B, T, H, Dh] with w [K, H, Dh]."""
    K = w.shape[0]
    return sum(shifted(x, K - 1 - j) * _f32(w[j]) for j in range(K))


def conv_by_head(x, w):
    """Equation 3's conv1 on x [B, T, H, Dh] with w [K, H, Dh, Dh]."""
    K = w.shape[0]
    return sum(jnp.einsum("bthk,hkc->bthc", shifted(x, K - 1 - j),
                          _f32(w[j])) for j in range(K))


def values(h, lp):
    """Equation 2: v [B, T, Hkv, Dh]."""
    v = jnp.einsum("btd,dhk->bthk", h, _f32(lp["c_wv"]))
    upper = jnp.arange(v.shape[2]) >= v.shape[2] // 2
    return jnp.where(upper[:, None], shifted(v, 1), v)


def queries_keys(h, lp, model):
    """Equations 1 and 3 to 6: q [B, T, Hq, Dh], k [B, T, Hkv, Dh]."""
    eps, theta, n = (model["rms_norm_eps"], model["rope_theta"],
                     model["rotated"])
    q0 = jnp.einsum("btd,dhk->bthk", h, _f32(lp["c_wq"]))
    k0 = jnp.einsum("btd,dhk->bthk", h, _f32(lp["c_wk"]))
    q1 = conv_by_head(conv_depthwise(q0, lp["c_conv0_q"]), lp["c_conv1_q"])
    k1 = conv_by_head(conv_depthwise(k0, lp["c_conv0_k"]), lp["c_conv1_k"])
    B, T, Hq, Dh = q0.shape
    Hkv = k0.shape[2]
    g = Hq // Hkv
    q = q1 + 0.5 * (q0 + jnp.repeat(k0, g, axis=2))
    k = k1 + 0.5 * (jnp.mean(q0.reshape(B, T, Hkv, g, Dh), axis=3) + k0)
    q = _rms(q, 1.0, eps)
    k = _rms(k, 1.0, eps) * _f32(lp["c_beta"])[:, None]
    q, k = (jnp.concatenate([rotate(x[..., :n], theta), x[..., n:]], -1)
            for x in (q, k))
    return q, k


def attention(h, lp, model):
    """The mixer of equations 1 to 7: h [B, T, d] normed hidden states ->
    [B, T, d]."""
    q, k = queries_keys(h, lp, model)
    v = values(h, lp)
    T, g = h.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(q_rows, k, v, i):
        s = jnp.einsum("bthk,bshk->bhts", q_rows, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhts,bshk->bthk", p, v)

    a = jnp.concatenate([
        block(q[:, at:at + size], k, v, at + jnp.arange(size)[:, None])
        for at, size in _blocks(T)], axis=1)
    return jnp.einsum("bthk,hkd->btd", a, _f32(lp["c_wo"]))


def router(u, lp, r_prev, model):
    """Equations 8 and 9's scores: (p [B, T, E], r_l [B, T, R])."""
    r = u @ _f32(lp["r_down"]) + _f32(lp["r_gamma"]) * r_prev
    y = _rms(r, lp["r_norm"], model["rms_norm_eps"])
    y = jax.nn.gelu(y @ _f32(lp["r_w1"]))
    y = jax.nn.gelu(y @ _f32(lp["r_w2"]))
    return jax.nn.softmax(y @ _f32(lp["r_w3"]), -1), r


def expert_layer(u, lp, r_prev, model):
    """Equations 8 and 9 on u [B, T, d]: (what the held experts give,
    r_l, tokens per expert [E])."""
    p, r = router(u, lp, r_prev, model)
    E = p.shape[-1]
    chosen = jnp.argmax(p + lax.stop_gradient(_f32(lp["expert_bias"])), -1)
    picked = chosen[..., None] == jnp.arange(E)  # [B, T, E]
    w = jnp.where(picked, p, 0.0)
    first = model["first_expert_held"]
    out = jnp.zeros_like(u)
    for e in range(lp["wg"].shape[0]):
        hidden = jax.nn.silu(u @ _f32(lp["wg"][e])) * (u @ _f32(lp["wu"][e]))
        out = out + w[..., first + e, None] * (hidden @ _f32(lp["wd"][e]))
    return out, r, jnp.sum(picked, axis=(0, 1))


def layer(x, r_prev, lp, *, model):
    """One layer: (x after it, r_l, tokens per expert [E])."""
    eps = model["rms_norm_eps"]
    a, b, c = _f32(lp["res1"])
    x = a * x + b + c * attention(_rms(x, lp["ln1"], eps), lp, model)
    out, r, load = expert_layer(_rms(x, lp["ln2"], eps), lp, r_prev, model)
    a, b, c = _f32(lp["res2"])
    return a * x + b + c * out, r, load


def layer_leaves(params, at):
    """Layer ``at``'s leaves out of their stacks."""
    return {name: params[name].reshape((-1,) + params[name].shape[2:])[at]
            for name in _LEAVES}


def nll_of(y, table, labels):
    """Cross-entropy [B, T] of normed hidden states y under the tied
    ``table`` [V, d], a block of rows at a time."""
    out = []
    for at, size in _blocks(y.shape[1]):
        logp = jax.nn.log_softmax(y[:, at:at + size] @ table.T, -1)
        out.append(-jnp.take_along_axis(
            logp, labels[:, at:at + size, None], -1)[..., 0])
    return jnp.concatenate(out, axis=1)


def forward(params, tokens, labels, model):
    """(every token's cross-entropy [B, T]; the tokens per expert of each
    layer [L, E]). The layers are one jitted function called once a
    layer, and the head another: beside a job's parameters and optimizer
    state the chip has no room for float32 copies of ten layers at once
    (called under an outer ``jax.jit`` they are inlined, and nothing
    changes but that)."""
    run = jax.jit(jax.checkpoint(functools.partial(layer, model=model)))
    head = jax.jit(jax.checkpoint(functools.partial(
        _head_nll, eps=model["rms_norm_eps"])))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        r = jnp.zeros(x.shape[:2] + (params["r_down"].shape[-1],),
                      jnp.float32)
        loads = []
        for at in range(model["num_hidden_layers"]):
            x, r, load = run(x, r, layer_leaves(params, at))
            loads.append(load)
        nll = head(x, params["final_ln"], params["embed"], labels)
    return nll, jnp.stack(loads)


def _head_nll(x, final_ln, embed, labels, eps):
    return nll_of(_rms(x, final_ln, eps), _f32(embed), labels)


def updated_bias(bias, load, rate):
    """Equation 11 on bias [..., E] with the tokens per expert ``load``."""
    load = _f32(load)
    delta = rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return _f32(bias) + delta - jnp.mean(delta, -1, keepdims=True)


def step_readings(params, tokens, labels, model):
    """What one training step is held to: the loss, every token's
    cross-entropy, the tokens per expert of each layer."""
    nll, load = forward(params, tokens, labels, model)
    return dict(loss=jnp.mean(nll), nll=nll, load=load)


def loss_and_grad(params, tokens, labels, model):
    """(loss, its gradient by every trained leaf, in the leaf's own
    dtype). The bias is no trained leaf and gets none."""
    biases = {k: params[k] for k in _BIASES}
    trained = {k: v for k, v in params.items() if k not in biases}

    def loss(weights):
        return jnp.mean(forward({**weights, **biases}, tokens, labels,
                                model)[0])

    return jax.value_and_grad(loss)(trained)
