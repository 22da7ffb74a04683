"""Plain float32 reference of the GLM-4.7-Flash decoder (``model_type``
``glm4_moe_lite``: ``zai-org/GLM-4.7-Flash`` ``config.json``; its block is
DeepSeek-V3's, arXiv:2412.19437 sections 2.1 and 2.2, at other numbers), as
one chip of a deployment holds it: the loss, every token's two
cross-entropies, the tokens each expert got in each layer, the router's
balancing bias after one application of its rule, and ``jax.grad`` of the
loss by every trained leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision: no
kernel, no sort, no scan over layers or experts, no sharding, nothing of
``horovod_tpu`` but its parameter *values* in its layouts. Attention is a
dense causal mask, formed a block of queries at a time so that ``[H, T,
T]`` scores never exist whole; every held expert is applied to every token
under a ``0 / w`` mask.

The layer, with ``rms(v; g) = v * rsqrt(mean(v^2) + eps) * g`` (eps 1e-5),
``h = rms(x; g_1)``, per token ``t`` and head ``i`` of H, no biases:

1. ``c_q = rms(h W_qa; g_q)`` [q_lora_rank]; ``q_i = c_q W_qb[i]`` [Dh],
   ``q_i = [q_i^nope (nope) ; q_i^rope (rope)]``, ``nope + rope = Dh``.
2. ``[c_kv (kv_lora_rank) ; k^rope (rope)] = h W_kva``; ``[k_i^nope (nope)
   ; v_i (Dh)] = rms(c_kv; g_kv) W_kvb[i]``. ``k^rope`` is one head for all
   H query heads.
3. ``q_i^rope`` and ``k^rope`` are rotated over all of their ``rope``
   channels at the token's position (rotate-half: channel ``c`` pairs with
   ``c + rope / 2``; base theta, no scaling); ``k_i = [k_i^nope ;
   k^rope]``.
4. ``o_i = softmax_{j <= t}(q_i . k_j / sqrt(Dh)) v``; ``x = x + concat_i(
   o_i) W_o``.
5. ``u = rms(x; g_2)``. Layer ``l < num_dense_layers``: ``m = (silu(u W_g)
   * (u W_u)) W_d``. Else ``s = sigmoid(u W_r)`` [E] (float32); ``S`` = the
   indices of the ``k`` largest ``s + b_l`` (``b_l`` the selection bias:
   indices only, no gradient); ``w_e = scale * s_e / (sum_{e' in S} s_e' +
   1e-20)`` for ``e`` in ``S``; ``m = shared(u) + sum_{e in S, e held} w_e
   expert_e(u)``, experts and shared expert gated SiLU MLPs. The sum in
   ``w`` is over all ``k`` picked experts; what the picked experts that
   are *not held* would add is left out: the chip's share of the layer.
   ``x = x + m``. No post-norms, no attention gate, no QK-norm, no
   auxiliary loss.
6. Main loss: ``CE(rms(x^L; g_f) W_head, t_{i+1})``, mean over all tokens,
   over the rows held here.
7. The multi-token-prediction module (one): ``g_i = [rms(x_i^L; g_h) ;
   rms(E[t_{i+1}]; g_e)] W_eh`` ([2 d] -> [d]; ``x^L`` before the final
   norm, the hidden half first), one more layer as 1 to 5 with leaves and
   a bias of its own (an expert layer), then ``CE(rms(.; g_s) W_head,
   t_{i+2})`` through the same ``E`` and ``W_head``, mean over the
   positions that have a ``t_{i+2}`` (all but a sequence's last; the
   layer runs on the last one too, and its router counts it). Loss = main
   + lambda * that.
8. After the step ``c_l`` = tokens per expert of layer ``l`` [E], ``delta =
   rate * sign(mean(c_l) - c_l)``, ``b_l += delta - mean(delta)``; the
   module's layer likewise.

``labels`` are ``t_{i+1}`` [B, T] as the batch gives them (the tokens
rolled by one); ``t_{i+2}`` is ``labels`` rolled by one more.

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
of the leaf's group a stage] are read as one axis): ``embed`` [V, d];
``ln1``, ``ln2`` [S, L, d]; ``l_wqa`` [S, L, d, rq], ``l_qnorm`` [S, L, rq],
``l_wqb`` [S, L, rq, H, Dh], ``l_wkva`` [S, L, d, rkv + rope], ``l_kvnorm``
[S, L, rkv], ``l_wkvb`` [S, L, rkv, H, nope + Dh], ``l_wo`` [S, L, H, Dh,
d]; the dense layers' ``wgu`` [S, Ld, d, 2, F], ``w2`` [S, Ld, F, d]; the
expert layers' ``router`` [S, Le, d, E], ``wg``, ``wu`` [S, Le, E_held, d,
f], ``wd`` [S, Le, E_held, f, d], ``shared_wgu`` [S, Le, d, 2, Fs],
``shared_w2`` [S, Le, Fs, d], ``expert_bias`` [S, Le, E]; ``final_ln`` [d];
``head`` [d, V]; the module's ``mtp_hnorm``, ``mtp_enorm``,
``mtp_final_ln`` [1, d], ``mtp_eh`` [1, 2 d, d] and its layer's leaves
under the stack's names after ``mtp_``, the one module in place of [S, L].

``model`` is a dict: ``num_hidden_layers``, ``num_dense_layers``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``rope_theta``, ``rms_norm_eps``, ``num_experts_per_tok``, ``route_scale``,
``mtp_loss_weight``, ``load_balance_coeff`` and ``first_expert_held`` (the
held experts are that one and the following, as many as ``wg`` holds).
Each layer and each block of queries is under ``jax.checkpoint``: that
changes what the backward pass keeps, not what is computed.
"""

import jax
import jax.numpy as jnp
from jax import lax

_ROWS = 256  # queries or logit rows formed at once
_LATENT = ("l_wqa", "l_qnorm", "l_wqb", "l_wkva", "l_kvnorm", "l_wkvb",
           "l_wo")
_GROUPS = {"latent": _LATENT, "mlp": ("wgu", "w2"),
           "moe": ("router", "wg", "wu", "wd", "shared_wgu", "shared_w2",
                   "expert_bias"),
           None: ("ln1", "ln2")}
_BIASES = ("expert_bias", "mtp_expert_bias")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * _f32(g)


def _blocks(T):
    size = _ROWS if T % _ROWS == 0 else T
    return [(at, size) for at in range(0, T, size)]


def rotate(x, theta):
    """Rotate-half rotary embedding of x [B, T, ..., n] over all n of its
    last channels at positions 0..T-1."""
    T, n = x.shape[1], x.shape[-1]
    half = n // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = angle.reshape((T,) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gated_mlp(u, wgu, w2):
    wgu = _f32(wgu)
    return (jax.nn.silu(u @ wgu[:, 0]) * (u @ wgu[:, 1])) @ _f32(w2)


def queries_keys_values(h, lp, model):
    """Equations 1 to 3 on normed h [B, T, d]: q, k, v, each [B, T, H,
    Dh]."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    rkv, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    c_q = _rms(h @ _f32(lp["l_wqa"]), lp["l_qnorm"], eps)
    q = jnp.einsum("btr,rhk->bthk", c_q, _f32(lp["l_wqb"]))
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], theta)], -1)
    down = h @ _f32(lp["l_wkva"])
    c_kv, k_rope = down[..., :rkv], rotate(down[..., rkv:], theta)
    up = jnp.einsum("btr,rhk->bthk", _rms(c_kv, lp["l_kvnorm"], eps),
                    _f32(lp["l_wkvb"]))
    H = up.shape[2]
    k = jnp.concatenate([
        up[..., :nope], jnp.repeat(k_rope[:, :, None], H, axis=2)], -1)
    return q, k, up[..., nope:]


def attention(h, lp, model):
    """The mixer of equations 1 to 4: h [B, T, d] normed hidden states ->
    [B, T, d]."""
    q, k, v = queries_keys_values(h, lp, model)
    T = h.shape[1]
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(q_rows, k, v, i):
        s = jnp.einsum("bthk,bshk->bhts", q_rows, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhts,bshk->bthk", p, v)

    a = jnp.concatenate([
        block(q[:, at:at + size], k, v, at + jnp.arange(size)[:, None])
        for at, size in _blocks(T)], axis=1)
    return jnp.einsum("bthk,hkd->btd", a, _f32(lp["l_wo"]))


def expert_layer(u, lp, model):
    """Equation 5's expert branch on u [B, T, d]: (what the held experts
    give, what the shared expert gives, tokens per expert [E])."""
    s = jax.nn.sigmoid(u @ _f32(lp["router"]))
    E, top_k = s.shape[-1], model["num_experts_per_tok"]
    chosen = lax.top_k(s + lax.stop_gradient(_f32(lp["expert_bias"])),
                       top_k)[1]
    picked = jnp.any(chosen[..., None] == jnp.arange(E), axis=-2)  # [B,T,E]
    w = jnp.where(picked, s, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * model["route_scale"]
    first = model["first_expert_held"]
    routed = jnp.zeros_like(u)
    for e in range(lp["wg"].shape[0]):
        hidden = jax.nn.silu(u @ _f32(lp["wg"][e])) * (u @ _f32(lp["wu"][e]))
        routed = routed + w[..., first + e, None] * (hidden
                                                     @ _f32(lp["wd"][e]))
    shared = _gated_mlp(u, lp["shared_wgu"], lp["shared_w2"])
    return routed, shared, jnp.sum(picked, axis=(0, 1))


def layer(x, lp, ffn, model):
    """Equations 1 to 5: (x after the layer, tokens per expert [E] or
    None for a dense layer)."""
    eps = model["rms_norm_eps"]
    x = x + attention(_rms(x, lp["ln1"], eps), lp, model)
    u = _rms(x, lp["ln2"], eps)
    if ffn == "mlp":
        return x + _gated_mlp(u, lp["wgu"], lp["w2"]), None
    routed, shared, load = expert_layer(u, lp, model)
    return x + routed + shared, load


def _layers(params, model):
    """Each layer's (feed-forward, its leaves), the stacks read by the
    layer's place in its group."""
    dense = model["num_dense_layers"]
    seen = {}
    for at in range(model["num_hidden_layers"]):
        ffn = "mlp" if at < dense else "moe"
        lp = {}
        for group in ("latent", ffn, None):
            row = at if group is None else seen.get(group, 0)
            for name in _GROUPS[group]:
                stack = params[name]
                lp[name] = stack.reshape((-1,) + stack.shape[2:])[row]
        for group in ("latent", ffn):
            seen[group] = seen.get(group, 0) + 1
        yield ffn, lp


def _nll(y, head, labels):
    """Cross-entropy [B, T] of normed hidden states y under ``head``, a
    block of rows at a time."""
    out = []
    for at, size in _blocks(y.shape[1]):
        logp = jax.nn.log_softmax(y[:, at:at + size] @ head, -1)
        out.append(-jnp.take_along_axis(
            logp, labels[:, at:at + size, None], -1)[..., 0])
    return jnp.concatenate(out, axis=1)


def forward(params, tokens, labels, model):
    """(every token's main cross-entropy [B, T]; its cross-entropy in the
    multi-token-prediction module [B, T], zero at a sequence's last
    position; the tokens per expert of each expert layer, the module's
    last, [Le + 1, E])."""
    eps = model["rms_norm_eps"]
    run = jax.checkpoint(layer, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        embed, head = _f32(params["embed"]), _f32(params["head"])
        x = embed[tokens]
        loads = []
        for ffn, lp in _layers(params, model):
            x, load = run(x, lp, ffn, model)
            if load is not None:
                loads.append(load)
        nll = _nll(_rms(x, params["final_ln"], eps), head, labels)

        mtp = {k[4:]: v[0] for k, v in params.items()
               if k.startswith("mtp_")}
        g = jnp.concatenate([_rms(x, mtp["hnorm"], eps),
                             _rms(embed[labels], mtp["enorm"], eps)],
                            -1) @ _f32(mtp["eh"])
        g, load = run(g, mtp, "moe", model)
        loads.append(load)
        mtp_nll = _nll(_rms(g, mtp["final_ln"], eps), head,
                       jnp.roll(labels, -1, axis=1))
        mtp_nll = mtp_nll * (jnp.arange(mtp_nll.shape[1])
                             < mtp_nll.shape[1] - 1)
    return nll, mtp_nll, jnp.stack(loads)


def loss_of(nll, mtp_nll, model):
    """Equations 6 and 7's sum: (loss, main mean, module's mean)."""
    main = jnp.mean(nll)
    B, T = mtp_nll.shape
    module = jnp.sum(mtp_nll) / (B * (T - 1))
    return main + model["mtp_loss_weight"] * module, main, module


def updated_bias(bias, load, rate):
    """Equation 8 on bias [..., E] with the tokens per expert ``load``."""
    load = _f32(load)
    delta = rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return _f32(bias) + delta - jnp.mean(delta, -1, keepdims=True)


def step_readings(params, tokens, labels, model):
    """What one training step is held to: the loss, every token's two
    cross-entropies, the tokens per expert of each expert layer (the
    module's last)."""
    nll, mtp_nll, load = forward(params, tokens, labels, model)
    return dict(loss=loss_of(nll, mtp_nll, model)[0], nll=nll,
                mtp_nll=mtp_nll, load=load)


def loss_and_grad(params, tokens, labels, model):
    """(loss, its gradient by every trained leaf, in the leaf's own
    dtype). The two biases are no trained leaves and get none."""
    biases = {k: params[k] for k in _BIASES}
    trained = {k: v for k, v in params.items() if k not in biases}

    def loss(weights):
        nll, mtp_nll, _ = forward({**weights, **biases}, tokens, labels,
                                  model)
        return loss_of(nll, mtp_nll, model)[0]

    return jax.value_and_grad(loss)(trained)
