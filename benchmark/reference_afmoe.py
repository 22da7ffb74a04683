"""Plain float32 reference of the Trinity decoder (``model_type``
``afmoe``: ``arcee-ai/Trinity-Mini`` ``config.json`` and the family's
public ``modeling_afmoe.py``), as one chip of a deployment holds it: the
loss, every token's cross-entropy, the tokens each expert got in each
layer, the router's balancing bias after one application of its rule,
and ``jax.grad`` of the loss by every trained leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision: no
kernel, no sort, no scan over layers or experts, no sharding, nothing of
``horovod_tpu`` but its parameter *values* in its layouts. Attention is a
dense ``[T, T]`` mask a layer, formed a block of queries at a time so
that it fits; every held expert is applied to every token under a
``0 / w`` mask.

The model, for ``x = E[tokens] * m`` [T, d] (``m`` the embedding
multiplier, sqrt(d) under ``mup_enabled``), ``rms(v; g) = v * rsqrt(mean(
v^2) + eps) * g``, layer ``l`` of kind ``sliding_attention`` or
``full_attention``, no biases anywhere:

1. ``h = rms(x; g_in)``; ``q = rms_head(h Wq; g_q)``, ``k = rms_head(h Wk;
   g_k)`` (RMS over each head's Dh channels, one weight vector for all
   heads), ``v = h Wv``; H query heads, Hkv key/value heads, each read by
   H / Hkv consecutive query heads. Sliding layers only: rotate-half RoPE
   (base theta) on q and k; full layers have no positions at all.
2. ``a = softmax(q k^T / sqrt(Dh) + mask) v`` with the mask ``j <= i``,
   and for sliding layers also ``i - j < W``.
3. ``x = x + rms((a * sigmoid(h Wgate)) Wo; g_post_attn)``.
4. ``u = rms(x; g_pre_mlp)``. For ``l < num_dense_layers``: ``m = (silu(u
   Wg) * (u Wu)) Wd``. Else ``s = sigmoid(u Wr)`` [E]; ``S`` = the indices
   of the ``k`` largest ``s + b_l`` (``b_l`` the balancing bias: indices
   only, no gradient); ``w_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20)``
   for ``e`` in ``S`` (``route_norm``; without it ``scale * s_e``); ``m =
   shared(u) + sum_{e in S, e held} w_e * expert_e(u)``, experts and
   shared expert gated SiLU MLPs. The sum in ``w`` is over all ``k`` picked
   experts; what the picked experts that are *not held* would add is left
   out: the chip's share of the layer.
5. ``x = x + rms(m; g_post_mlp)``.
6. ``logits = rms(x; g_f) Whead`` over the rows held here; loss = mean
   token cross-entropy over them, no auxiliary term.
7. After the step ``c_l`` = tokens per expert of layer ``l`` [E], ``delta =
   rate * sign(mean(c_l) - c_l)``, ``b_l += delta - mean(delta)``.

Each mechanism is there if its leaf is (``wgate``; ``gq`` and ``gk``;
``ln1_post`` and ``ln2_post``; ``shared_wgu`` and ``shared_w2``;
``expert_bias``), so that the tests can hold each alone.

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
of the leaf's group a stage] are read as one axis): ``embed`` [V, d];
``ln1``, ``ln2``, ``ln1_post``, ``ln2_post`` [S, L, d]; ``wq``, ``wgate``
[S, L, d, H, Dh]; ``wkv`` [S, L, d, 2, Hkv, Dh]; ``gq``, ``gk`` [S, L, Dh];
``wo`` [S, L, H, Dh, d]; the dense layers' ``wgu`` [S, Ld, d, 2, F], ``w2``
[S, Ld, F, d]; the expert layers' ``router`` [S, Le, d, E], ``wg``, ``wu``
[S, Le, E_held, d, f], ``wd`` [S, Le, E_held, f, d], ``shared_wgu`` [S, Le,
d, 2, Fs], ``shared_w2`` [S, Le, Fs, d], ``expert_bias`` [S, Le, E];
``final_ln`` [d]; ``head`` [d, V].

``model`` is a dict: ``layer_types`` (the layers run), ``num_dense_layers``,
``sliding_window``, ``rope_theta``, ``rms_norm_eps``,
``num_experts_per_tok``, ``route_norm``, ``route_scale``,
``embedding_multiplier``, ``load_balance_coeff``, and ``first_expert_held``
(the held experts are that one and the following, as many as ``wg``
holds). Each layer and each block of queries is under ``jax.checkpoint``:
that changes what the backward pass keeps, not what is computed.
"""

import jax
import jax.numpy as jnp
from jax import lax

_ROWS = 256  # queries or logit rows formed at once
_GROUPS = {"attention": ("wq", "wkv", "wo", "wgate", "gq", "gk"),
           "mlp": ("wgu", "w2"),
           "moe": ("router", "wg", "wu", "wd", "shared_wgu", "shared_w2",
                   "expert_bias"),
           None: ("ln1", "ln2", "ln1_post", "ln2_post")}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * _f32(g)


def _blocks(T):
    size = _ROWS if T % _ROWS == 0 else T
    return [(at, size) for at in range(0, T, size)]


def _rope(x, theta):
    """Rotate-half rotary embedding of x [B, T, H, Dh] at positions 0..T-1."""
    T, Dh = x.shape[1], x.shape[3]
    half = Dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gated_mlp(u, wgu, w2):
    wgu = _f32(wgu)
    return (jax.nn.silu(u @ wgu[:, 0]) * (u @ wgu[:, 1])) @ _f32(w2)


def attention(h, lp, kind, model):
    """The mixer of equations 1 to 3, before the output projection's
    post-norm: h [B, T, d] normed hidden states -> [B, T, d]."""
    eps = model["rms_norm_eps"]
    T = h.shape[1]
    q = jnp.einsum("btd,dhk->bthk", h, _f32(lp["wq"]))
    wkv = _f32(lp["wkv"])
    k, v = (jnp.einsum("btd,dhk->bthk", h, wkv[:, c]) for c in range(2))
    if "gq" in lp:
        q, k = _rms(q, lp["gq"], eps), _rms(k, lp["gk"], eps)
    if kind == "sliding_attention":
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    repeat = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, repeat, axis=2), jnp.repeat(v, repeat, axis=2)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(q_rows, k, v, i):
        mask = j <= i
        if kind == "sliding_attention":
            mask = mask & (i - j < model["sliding_window"])
        s = jnp.einsum("bthk,bshk->bhts", q_rows, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhts,bshk->bthk", p, v)

    a = jnp.concatenate([
        block(q[:, at:at + size], k, v, at + jnp.arange(size)[:, None])
        for at, size in _blocks(T)], axis=1)
    if "wgate" in lp:
        a = a * jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", h,
                                          _f32(lp["wgate"])))
    return jnp.einsum("bthk,hkd->btd", a, _f32(lp["wo"]))


def expert_layer(u, lp, model):
    """Equation 4's expert branch on u [B, T, d]: (what the held experts
    give, what the shared expert gives (zero without one), tokens per
    expert [E])."""
    s = jax.nn.sigmoid(u @ _f32(lp["router"]))
    E, top_k = s.shape[-1], model["num_experts_per_tok"]
    biased = s + lax.stop_gradient(_f32(lp["expert_bias"])) \
        if "expert_bias" in lp else s
    chosen = lax.top_k(biased, top_k)[1]
    picked = jnp.any(chosen[..., None] == jnp.arange(E), axis=-2)  # [B,T,E]
    w = jnp.where(picked, s, 0.0)
    if model["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * model["route_scale"]
    first = model["first_expert_held"]
    routed = jnp.zeros_like(u)
    for e in range(lp["wg"].shape[0]):
        hidden = jax.nn.silu(u @ _f32(lp["wg"][e])) * (u @ _f32(lp["wu"][e]))
        routed = routed + w[..., first + e, None] * (hidden
                                                     @ _f32(lp["wd"][e]))
    shared = _gated_mlp(u, lp["shared_wgu"], lp["shared_w2"]) \
        if "shared_wgu" in lp else jnp.zeros_like(u)
    return routed, shared, jnp.sum(picked, axis=(0, 1))


def _layers(params, model):
    """Each layer's (kind, feed-forward, its leaves), the stacks read by
    the layer's place in its group."""
    dense = model["num_dense_layers"]
    seen = {}
    for at, kind in enumerate(model["layer_types"]):
        ffn = "mlp" if at < dense else "moe"
        lp = {}
        for group in ("attention", ffn, None):
            row = at if group is None else seen.get(group, 0)
            for name in _GROUPS[group]:
                if name in params:
                    stack = params[name]
                    lp[name] = stack.reshape((-1,) + stack.shape[2:])[row]
        for group in ("attention", ffn):
            seen[group] = seen.get(group, 0) + 1
        yield kind, ffn, lp


def forward(params, tokens, labels, model):
    """Every token's cross-entropy [B, T] on ``tokens``, ``labels`` [B, T]
    and the tokens per expert of each expert layer [Le, E]."""
    eps = model["rms_norm_eps"]

    def layer(x, lp, kind, ffn):
        a = attention(_rms(x, lp["ln1"], eps), lp, kind, model)
        x = x + (_rms(a, lp["ln1_post"], eps) if "ln1_post" in lp else a)
        u = _rms(x, lp["ln2"], eps)
        if ffn == "mlp":
            m, load = _gated_mlp(u, lp["wgu"], lp["w2"]), None
        else:
            routed, shared, load = expert_layer(u, lp, model)
            m = routed + shared
        x = x + (_rms(m, lp["ln2_post"], eps) if "ln2_post" in lp else m)
        return x, load

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens] * model["embedding_multiplier"]
        loads = []
        for kind, ffn, lp in _layers(params, model):
            x, load = jax.checkpoint(layer, static_argnums=(2, 3))(
                x, lp, kind, ffn)
            if load is not None:
                loads.append(load)
        y = _rms(x, params["final_ln"], eps)
        head = _f32(params["head"])
        nll = []
        for at, size in _blocks(y.shape[1]):
            logp = jax.nn.log_softmax(y[:, at:at + size] @ head, -1)
            nll.append(-jnp.take_along_axis(
                logp, labels[:, at:at + size, None], -1)[..., 0])
    return jnp.concatenate(nll, axis=1), jnp.stack(loads)


def updated_bias(bias, load, rate):
    """Equation 7 on bias [..., E] with the tokens per expert ``load``."""
    load = _f32(load)
    delta = rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return _f32(bias) + delta - jnp.mean(delta, -1, keepdims=True)


def step_readings(params, tokens, labels, model):
    """What one training step is held to: the loss, every token's
    cross-entropy, the tokens per expert of each expert layer, and the
    bias after the rule's one application (None without a bias)."""
    nll, load = forward(params, tokens, labels, model)
    bias = None
    if "expert_bias" in params:
        bias = updated_bias(params["expert_bias"],
                            load.reshape(params["expert_bias"].shape),
                            model["load_balance_coeff"])
    return dict(loss=jnp.mean(nll), nll=nll, load=load, bias=bias)


def loss_and_grad(params, tokens, labels, model):
    """(loss, its gradient by every trained leaf, in the leaf's own
    dtype). ``expert_bias`` is no trained leaf and gets none."""
    bias = {k: params[k] for k in ("expert_bias",) if k in params}
    trained = {k: v for k, v in params.items() if k not in bias}
    return jax.value_and_grad(lambda weights: jnp.mean(forward(
        {**weights, **bias}, tokens, labels, model)[0]))(trained)
