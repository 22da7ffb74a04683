"""Plain float32 reference of the Ling-3.0-flash decoder (``model_type``
``bailing_hybrid``: ``inclusionAI/Ling-3.0-flash`` ``config.json``), as one
chip of a deployment holds it: the loss, every token's two cross-entropies,
the tokens each expert got in each layer, the router's balancing bias after
one application of its rule, and ``jax.grad`` of the loss by every trained
leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision: no
kernel, no chunk, no sort, no scan over layers or experts, no sharding,
nothing of ``horovod_tpu`` but its parameter *values* in its layouts. KDA
is the recurrence over time, one token a step of a ``lax.scan`` (a loop
over tokens written as the language has it; nothing is cut into chunks).
Latent attention is a dense causal mask, formed a block of queries at a
time so that ``[H, T, T]`` scores never exist whole. Every held expert is
applied to every token under a ``0 / w`` mask.

Each line is marked *row* (the published config's key says it), *paper*
(the cited description does) or *assumed* (neither does; the reading is the
configuration file's ``assumed`` entry). ``rms(v; g) = v * rsqrt(mean(v^2)
+ eps) * g`` (eps 1e-6, *row*), ``h = rms(x; g_1)``, per token ``t``, H = 32
heads, no biases but the decay's.

KDA mixer (Kimi Linear, arXiv:2510.26692 section 3), K = 128 channels a
head for keys and values alike (*row*: ``head_dim``,
``num_kv_heads_for_linear_attn`` 0 = as many as query heads):

1. ``q~, k~, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h
   W_v))`` [T, H, K]; ``conv4`` a causal depth-wise convolution of 4 taps,
   zeros before the first token (*row*: ``short_conv_kernel_size``,
   ``linear_silu``).
2. ``q = K^-1/2 q~ / |q~|``, ``k = k~ / |k~|`` over a head's channels
   (*paper*; the row's ``use_qk_norm`` read as this in a KDA layer, and
   1e-6 under the root: *assumed*).
3. ``a = h W_f + b`` [H, K]; ``g = L sigmoid(exp(A_h) a)``, L =
   ``kda_lower_bound`` = -5, ``A_h`` a scalar a head; the decay ``alpha =
   exp(g)`` in (e^-5, 1) a channel (*row*: ``kda_safe_gate``,
   ``kda_lower_bound``; the closed form *assumed*).
4. ``beta = sigmoid(h W_beta)`` [H] (*paper*).
5. ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
   v_t^T``, ``S_0 = 0`` [K, K] a head; ``o_t = S_t^T q_t`` (*paper*).
6. ``y = concat_i( rms(o_i; g_o) * sigmoid(h W_g)_i ) W_o``, the norm over
   a head's channels with one weight [K] (*row*: ``group_norm_size`` 1;
   ``W_g`` full rank, ``no_kda_lora``). No rotation (*paper*).

MLA mixer (DeepSeek-V2's, arXiv:2405.04434, with no query rank):

7. ``q_i = h W_q[i] = [q_i^nope (128) ; q_i^rope (64)]`` (*row*:
   ``q_lora_rank`` null). ``[c (512) ; k^rope (64)] = h W_kva``; ``[k_i^nope
   (128) ; v_i (128)] = rms(c; g_kv) W_kvb[i]`` (*row*); ``k_i = [k_i^nope ;
   k^rope]``, the one ``k^rope`` for all heads.
8. ``q_i = rms(q_i; g_q)``, ``k_i = rms(k_i; g_k)`` over a head's 192
   channels, one weight each for all heads, before the rotation (*row*:
   ``use_qk_norm``; where it sits in a latent head *assumed*).
9. The last 64 channels of ``q_i`` and ``k_i`` rotated at the token's
   position, rotate-half, theta 6e6 (*row*; the layout *assumed*).
10. ``o_i = softmax_{j <= t}(q_i . k_j / sqrt(192)) v_j`` [128]; ``y =
    concat_i( sigmoid(h W_gate)_i o_i ) W_o``, one gate a head (*row*:
    ``gated_attention_proj_granularity_type`` head_wise).

Feed-forward, ``u = rms(x; g_2)``:

11. Layer ``l < first_k_dense_replace``: ``m = (silu(u W_g) * (u W_u))
    W_d`` at 6,144 (*row*). Else ``s = sigmoid(u W_r)`` [512] (float32);
    ``n_group`` = 8 groups of 64 consecutive experts; a group's score the
    sum of its two largest ``s + b_l``; the ``topk_group`` = 4 best groups
    kept; ``S`` = the 8 largest ``s + b_l`` among the kept groups' experts
    (*row* + *paper*: DeepSeek-V3's ``noaux_tc``, arXiv:2412.19437 section
    2.1.2; ``b_l`` the selection bias: indices only, no gradient); ``w_e =
    2.5 s_e / (sum_{e' in S} s_e' + 1e-20)`` (*row*: ``norm_topk_prob``,
    ``routed_scaling_factor``); ``m = shared(u) + sum_{e in S, e held} w_e
    expert_e(u)``, gated SiLU MLPs of 768. What the picked experts that are
    *not held* would add is left out: the chip's share. The row's
    ``expert_swiglu_limit_list`` and ``share_expert_swiglu_limit_list`` are
    0 in layers 0 to 33: no clamp runs in the layers kept here.
12. Main loss: ``CE(rms(x^L; g_f) W_head, t_{i+1})``, mean over all tokens,
    over the rows held here.
13. The multi-token-prediction module (one; ``glm-4.7-flash``'s): ``g_i =
    [rms(x_i^L; g_h) ; rms(E[t_{i+1}]; g_e)] W_eh``, one more sparse layer
    whose mixer is MLA (*row*: ``mtp_use_kda`` false), ``CE(rms(.; g_s)
    W_head, t_{i+2})`` over the positions that have a ``t_{i+2}``. Loss =
    main + lambda * that, lambda 0.3 (*assumed*; the published
    ``mtp_loss_scaling_factor`` 0 would train nothing in it).
14. After the step ``b_l += delta - mean(delta)``, ``delta = rate *
    sign(mean(c_l) - c_l)``, ``c_l`` the tokens per expert, rate 0.001
    (*assumed*, as ``glm-4.7-flash``'s).

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
of the leaf's group a stage] are read as one): the KDA mixers' ``k_wqkv``
[d, 3, H, K], ``k_conv`` [4, 3, H, K], ``k_wf`` [d, H, K], ``k_fb`` [H, K],
``k_A`` [H], ``k_wbeta`` [d, H], ``k_wg`` [d, H, K], ``k_norm`` [K],
``k_wo`` [H, K, d]; the latent mixers' ``l_wq`` [d, H, 192], ``l_wkva`` [d,
576], ``l_kvnorm`` [512], ``l_wkvb`` [512, H, 256], ``l_gq``, ``l_gk``
[192], ``l_wgate`` [d, H], ``l_wo`` [H, 128, d]; everything else as
``reference_glm_lite`` lists it.

``model`` is a dict: ``layer_types`` (the stack's kinds), ``mtp_layer_type``,
``num_dense_layers``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``rope_theta``, ``rms_norm_eps``, ``kda_gate_floor``, ``n_group``,
``topk_group``, ``num_experts_per_tok``, ``route_scale``,
``mtp_loss_weight``, ``load_balance_coeff``, ``first_expert_held``. Each
layer and each block of queries is under ``jax.checkpoint``: that changes
what the backward pass keeps, not what is computed.
"""

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference_glm_lite import (  # the same mathematics
    _blocks, _f32, _gated_mlp, _nll, _rms, loss_of, rotate, updated_bias)

__all__ = ["forward", "loss_and_grad", "loss_of", "step_readings",
           "updated_bias"]

_KDA = ("k_wqkv", "k_conv", "k_wf", "k_fb", "k_A", "k_wbeta", "k_wg",
        "k_norm", "k_wo")
_LATENT = ("l_wq", "l_wkva", "l_kvnorm", "l_wkvb", "l_gq", "l_gk",
           "l_wgate", "l_wo")
_GROUPS = {"kda": _KDA, "latent_attention": _LATENT, "mlp": ("wgu", "w2"),
           "moe": ("router", "wg", "wu", "wd", "shared_wgu", "shared_w2",
                   "expert_bias"),
           None: ("ln1", "ln2")}
_BIASES = ("expert_bias", "mtp_expert_bias")


def conv_causal(x, w):
    """Equation 1's ``conv4``: ``y_t = sum_j w[j] x_{t - 3 + j}`` of x [B,
    T, ...] with w [taps, ...], zeros before the first token."""
    taps, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (taps - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    return sum(xp[:, j:j + T] * w[j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """Equation 5, a token at a time: q, k, v, g [B, T, H, K], beta [B, T,
    H] -> o [B, T, H, K]."""
    B, _, H, K = q.shape

    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None] * S
        write = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., None] * write[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    _, o = lax.scan(token, jnp.zeros((B, H, K, v.shape[-1]), jnp.float32),
                    [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)])
    return jnp.moveaxis(o, 0, 1)


def kda_operands(h, lp, model):
    """Equations 1 to 4 on normed h [B, T, d]: (q, k, v, g [B, T, H, K],
    beta [B, T, H])."""
    qkv = jnp.einsum("btd,dchk->btchk", h, _f32(lp["k_wqkv"]))
    qkv = jax.nn.silu(conv_causal(qkv, _f32(lp["k_conv"])))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def unit(x):
        return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)

    a = jnp.einsum("btd,dhk->bthk", h, _f32(lp["k_wf"])) + _f32(lp["k_fb"])
    g = model["kda_gate_floor"] * jax.nn.sigmoid(
        jnp.exp(_f32(lp["k_A"]))[:, None] * a)
    beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", h, _f32(lp["k_wbeta"])))
    return unit(q) * q.shape[-1] ** -0.5, unit(k), v, g, beta


def kda(h, lp, model):
    """The mixer of equations 1 to 6: h [B, T, d] -> [B, T, d]."""
    o = delta_rule(*kda_operands(h, lp, model))
    gate = jax.nn.sigmoid(jnp.einsum("btd,dhk->bthk", h, _f32(lp["k_wg"])))
    y = _rms(o, lp["k_norm"], model["rms_norm_eps"]) * gate
    return jnp.einsum("bthk,hkd->btd", y, _f32(lp["k_wo"]))


def queries_keys_values(h, lp, model):
    """Equations 7 to 9 on normed h [B, T, d]: q, k [B, T, H, 192], v [B,
    T, H, 128]."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    rkv, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    q = jnp.einsum("btd,dhk->bthk", h, _f32(lp["l_wq"]))
    down = h @ _f32(lp["l_wkva"])
    up = jnp.einsum("btr,rhk->bthk", _rms(down[..., :rkv], lp["l_kvnorm"],
                                         eps), _f32(lp["l_wkvb"]))
    H = up.shape[2]
    k = jnp.concatenate([
        up[..., :nope], jnp.repeat(down[:, :, None, rkv:], H, axis=2)], -1)
    q, k = _rms(q, lp["l_gq"], eps), _rms(k, lp["l_gk"], eps)
    q, k = (jnp.concatenate([x[..., :nope], rotate(x[..., nope:], theta)],
                            -1) for x in (q, k))
    return q, k, up[..., nope:]


def latent_attention(h, lp, model):
    """The mixer of equations 7 to 10: h [B, T, d] -> [B, T, d]."""
    q, k, v = queries_keys_values(h, lp, model)
    B, T, H, D = q.shape
    size = _blocks(T)[0][1]
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(rows):
        q_rows, i = rows  # [B, size, H, D], the queries' positions
        s = jnp.einsum("bthk,bshk->bhts", q_rows, k) * D ** -0.5
        p = jax.nn.softmax(
            jnp.where((j <= i[:, None])[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhts,bshk->bthk", p, v)

    # One block of queries after another: [H, size, T] scores at a time.
    a = lax.map(block, (
        jnp.moveaxis(q.reshape(B, T // size, size, H, D), 1, 0),
        jnp.arange(T).reshape(T // size, size)))
    a = jnp.moveaxis(a, 0, 1).reshape(B, T, H, v.shape[-1])
    gate = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", h, _f32(lp["l_wgate"])))
    return jnp.einsum("bthk,hkd->btd", a * gate[..., None], _f32(lp["l_wo"]))


def selection(s, bias, model):
    """Equation 11's choice on scores s [..., E] under the bias [E]: which
    experts each token picks, bool [..., E], by the definition: a group's
    score from a sort of its entries, the kept groups from a sort of the
    scores, the picks from a sort of the kept entries."""
    n_group, keep = model["n_group"], model["topk_group"]
    top_k = model["num_experts_per_tok"]
    E = s.shape[-1]
    biased = s + lax.stop_gradient(_f32(bias))
    groups = biased.reshape(biased.shape[:-1] + (n_group, E // n_group))
    score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    # Rank of each group among the groups, the largest score first; ties
    # to the group that comes first, as ``lax.top_k`` has them.
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    allowed = jnp.repeat(rank < keep, E // n_group, axis=-1)
    masked = jnp.where(allowed, biased, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    return jnp.argsort(order, axis=-1, stable=True) < top_k


def expert_layer(u, lp, model):
    """Equation 11's expert branch on u [B, T, d]: (what the held experts
    give, what the shared expert gives, tokens per expert [E])."""
    s = jax.nn.sigmoid(u @ _f32(lp["router"]))
    picked = selection(s, lp["expert_bias"], model)
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


_MIXERS = {"kda": kda, "latent_attention": latent_attention}


def layer(x, lp, kind, ffn, model):
    """One layer: (x after it, tokens per expert [E] or None for a dense
    layer)."""
    eps = model["rms_norm_eps"]
    x = x + _MIXERS[kind](_rms(x, lp["ln1"], eps), lp, model)
    u = _rms(x, lp["ln2"], eps)
    if ffn == "mlp":
        return x + _gated_mlp(u, lp["wgu"], lp["w2"]), None
    routed, shared, load = expert_layer(u, lp, model)
    return x + routed + shared, load


def _layers(params, model):
    """Each layer's (mixer, feed-forward, its leaves), the stacks read by
    the layer's place in its group."""
    dense = model["num_dense_layers"]
    seen = {}
    for at, kind in enumerate(model["layer_types"]):
        ffn = "mlp" if at < dense else "moe"
        lp = {}
        for group in (kind, ffn, None):
            row = at if group is None else seen.get(group, 0)
            for name in _GROUPS[group]:
                stack = params[name]
                lp[name] = stack.reshape((-1,) + stack.shape[2:])[row]
        for group in (kind, ffn):
            seen[group] = seen.get(group, 0) + 1
        yield kind, ffn, lp


def forward(params, tokens, labels, model):
    """(every token's main cross-entropy [B, T]; its cross-entropy in the
    multi-token-prediction module [B, T], zero at a sequence's last
    position; the tokens per expert of each expert layer, the module's
    last, [Le + 1, E])."""
    eps = model["rms_norm_eps"]
    # The layers are one jitted function called once a layer, the head
    # and the module's front two more: beside a job's parameters and
    # optimizer state the chip has no room for float32 copies of eight
    # layers at once (called under an outer ``jax.jit`` they are inlined,
    # and nothing changes but that).
    run = jax.jit(jax.checkpoint(layer, static_argnums=(2, 3, 4)),
                  static_argnums=(2, 3, 4))
    head_nll = jax.jit(jax.checkpoint(_head_nll, static_argnums=(4,)),
                       static_argnums=(4,))
    frozen = _Frozen(model)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        loads = []
        for kind, ffn, lp in _layers(params, model):
            x, load = run(x, lp, kind, ffn, frozen)
            if load is not None:
                loads.append(load)
        nll = head_nll(x, params["final_ln"], params["head"], labels, eps)

        mtp = {k[4:]: v[0] for k, v in params.items()
               if k.startswith("mtp_")}
        g = jax.jit(_module_input, static_argnums=(4,))(
            x, params["embed"][labels], mtp["hnorm"], mtp["enorm"], eps,
            mtp["eh"])
        g, load = run(g, mtp, model["mtp_layer_type"], "moe", frozen)
        loads.append(load)
        mtp_nll = head_nll(g, mtp["final_ln"], params["head"],
                           jnp.roll(labels, -1, axis=1), eps)
        mtp_nll = mtp_nll * (jnp.arange(mtp_nll.shape[1])
                             < mtp_nll.shape[1] - 1)
    return nll, mtp_nll, jnp.stack(loads)


def _head_nll(x, final_ln, head, labels, eps):
    return _nll(_rms(x, final_ln, eps), _f32(head), labels)


def _module_input(x, embedded, g_h, g_e, eps, eh):
    """Equation 13's ``g``: the hidden half first."""
    return jnp.concatenate([_rms(x, g_h, eps),
                            _rms(_f32(embedded), g_e, eps)], -1) @ _f32(eh)


class _Frozen(dict):
    """``model`` as a static argument of ``jax.checkpoint``: hashed by what
    it holds."""

    def __hash__(self):
        return hash(tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                                 for k, v in self.items())))


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
