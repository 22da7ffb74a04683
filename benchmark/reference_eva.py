"""Plain float32 reference of the EvaByte decoder (``model_type``
``evabyte``: ``EvaByte/EvaByte`` ``config.json``; EVA attention, Zheng,
Yuan, Wang and Kong, "Efficient Attention via Control Variates",
arXiv:2302.04542, in the deterministic form the released model runs): the
loss, every position's cross-entropy under each of the prediction heads,
and ``jax.grad`` of the loss by every leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision: no
kernel, no scan over layers, no sharding, nothing of ``horovod_tpu`` but
its parameter *values* in its layouts. Attention is computed a window of
queries at a time, dense scores of that window against its own keys and
against every chunk summary under a mask (2,048 x 3,968 visible scores a
head at most at the timed size), so ``[T, T]`` scores never exist; the
MLP and the head a block of tokens at a time.

Each line is marked *row* (a key of the catalog's ``config``), *paper*
(arXiv:2302.04542) or *assumed* (the configuration file's ``assumed`` has
the same list). Pre-norm decoder on the stream ``x`` [T, d], float32
(``fp32_skip_add``, *row*; *assumed*: the sum is taken and kept in
float32: rounded back to bf16 the flag would change nothing). ``d`` 4,096,
``H`` 32 heads of ``D`` 128 with as many key/value heads, width 11,008,
320 rows, no biases (*row*).

1. ``rms(v; g) = v * rsqrt(mean(v^2) + eps) * (1 + g)``, eps 1e-5
   (``norm_add_unit_offset``, ``rms_norm_eps``: *row*).
2. ``x <- x + Attn(rms(x; g_1))``; ``x <- x + W_d (silu(W_g h') * W_u
   h')``, ``h' = rms(x; g_2)`` (``hidden_act`` silu, ``intermediate_size``:
   *row*).
3. ``q, k, v = h W_q, h W_k, h W_v`` [T, H, D]; ``q``, ``k`` rotated over
   the whole head at the token's position, rotate-half (channel ``c``
   pairs with ``c + D / 2``), theta 100,000, no scaling (``rope_theta``,
   ``rope_scaling`` null: *row*).
4. Positions fall into windows of ``W`` = 2,048 and chunks of ``C`` = 16
   (``window_size``, ``chunk_size``: *row*), 128 chunks a window. Each head
   has two learned vectors ``mu``, ``phi`` [D] (*assumed*: not keys of the
   row; initialised as the other leaves). For chunk ``c`` with tokens
   ``P_c``: ``k~_c = sum_{m in P_c} softmax_m(mu . k_m) k_m``, ``v~_c =
   sum_{m in P_c} softmax_m(phi . k_m) v_m``, keys after rotation (*paper*:
   the control variates' per-chunk summaries, section 4, with the
   importance weights a learned softmax in the deterministic form).
5. Query ``i`` in window ``w = i // W`` sees the exact set ``E_i = {m : m
   // W = w, m <= i}`` and the summaries ``R_i = {c : c < (W / C) w}``:
   every chunk of every earlier window, none of its own (*paper*). ``s_im =
   q_i . k_m / sqrt(D)``, ``r_ic = q_i . k~_c / sqrt(D)``, ``Z_i = sum_E
   e^{s_im} + sum_R e^{r_ic}``, ``o_i = (sum_E e^{s_im} v_m + sum_R
   e^{r_ic} v~_c) / Z_i``: one softmax over both (``mixedp_attn``: in
   float32, *row*); ``Attn(h) = concat(o) W_o``.
6. Head: ``logits_j = rms(x_L; g_f) W_head[j]``, ``j`` = 0 .. 7, 320 rows
   each, float32 (``num_pred_heads``, ``fp32_logits``: *row*); loss = mean
   over ``j`` and positions of the cross-entropy of head ``j`` against
   byte ``t + 1 + j`` (*assumed*: equal weights; the row gives the count).
   ``labels`` are the bytes shifted by one, as the other cells make theirs
   (rolled: the last position's wraps to the first), head ``j``'s the
   labels shifted by ``j`` more.

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
a stage] of a stack are merged here): ``embed`` [V, d]; ``ln1``, ``ln2``
[L, d]; ``e_wqkv`` [L, d, 3, H, D]; ``e_mu``, ``e_phi`` [L, H, D]; ``e_wo``
[L, H, D, d]; ``wgu`` [L, d, 2, F] (gate, up); ``w2`` [L, F, d];
``final_ln`` [d]; ``head`` [d, P V], head ``j``'s columns ``j V .. (j + 1)
V``. ``model``: ``num_hidden_layers``, ``rms_norm_eps``, ``rope_theta``,
``window_size``, ``chunk_size``, ``num_pred_heads``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_LEAVES = ("ln1", "ln2", "e_wqkv", "e_mu", "e_phi", "e_wo", "wgu", "w2")
# Tokens a block of the MLP and of the head.
_BLOCK = 2048


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * (1.0 + _f32(g))


def _rotate(x, theta):
    """Equation 3's rotation of x [B, T, H, D] at positions 0 .. T - 1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, mu, phi, chunk):
    """Equation 4: (k~, v~) [B, T / C, H, D] of rotated k and v [B, T, H,
    D] under mu, phi [H, D]."""
    B, T, H, D = k.shape
    kc, vc = (x.reshape(B, T // chunk, chunk, H, D) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, mu), axis=2)
    b = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi), axis=2)
    return (jnp.einsum("bnch,bnchd->bnhd", a, kc),
            jnp.einsum("bnch,bnchd->bnhd", b, vc))


def attention(h, lp, model):
    """Equations 3 to 5 on normed h [B, T, d]."""
    W, C = model["window_size"], model["chunk_size"]
    B, T, _ = h.shape
    W = min(W, T)
    qkv = jnp.einsum("btd,dchk->cbthk", h, _f32(lp["e_wqkv"]))
    q = _rotate(qkv[0], model["rope_theta"])
    k = _rotate(qkv[1], model["rope_theta"])
    v = qkv[2]
    k_sum, v_sum = summaries(k, v, _f32(lp["e_mu"]), _f32(lp["e_phi"]), C)
    scale = q.shape[-1] ** -0.5
    n, per = T // W, W // C
    causal = jnp.tril(jnp.ones((W, W), bool))
    chunk_window = jnp.arange(T // C) // per  # the window a chunk lies in

    def one_window(w):
        def cut(x):
            return lax.dynamic_slice_in_dim(x, w * W, W, axis=1)

        qw = cut(q)
        s = jnp.einsum("bqhd,bkhd->bhqk", qw, cut(k)) * scale
        r = jnp.einsum("bqhd,bchd->bhqc", qw, k_sum) * scale
        s = jnp.where(causal, s, -jnp.inf)
        r = jnp.where(chunk_window < w, r, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([s, r], -1), axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :W], cut(v))
                + jnp.einsum("bhqc,bchd->bqhd", p[..., W:], v_sum))

    o = lax.map(one_window, jnp.arange(n))          # [n, B, W, H, D]
    o = o.transpose(1, 0, 2, 3, 4).reshape(q.shape)
    return jnp.einsum("bthk,hkd->btd", o, _f32(lp["e_wo"]))


def _by_blocks(fn, x):
    """``fn`` on consecutive blocks of ``_BLOCK`` tokens of x [B, T, ...],
    joined along the tokens."""
    T = x.shape[1]
    return jnp.concatenate([fn(x[:, at:at + _BLOCK])
                            for at in range(0, T, _BLOCK)], axis=1)


def mlp(h, lp):
    wgu, w2 = _f32(lp["wgu"]), _f32(lp["w2"])
    return _by_blocks(
        lambda u: (jax.nn.silu(u @ wgu[:, 0]) * (u @ wgu[:, 1])) @ w2, h)


def layer(x, lp, *, model):
    """Equation 2: the stream after one layer."""
    eps = model["rms_norm_eps"]
    x = x + attention(_rms(x, lp["ln1"], eps), lp, model)
    return x + mlp(_rms(x, lp["ln2"], eps), lp)


def layer_leaves(params, at):
    """Layer ``at``'s leaves out of their stacks."""
    return {name: params[name].reshape((-1,) + params[name].shape[2:])[at]
            for name in _LEAVES}


def head_labels(labels, heads):
    """[B, T] -> [B, T, P]: head j's labels, the labels shifted by j."""
    return jnp.stack([jnp.roll(labels, -j, axis=1) for j in range(heads)],
                     -1)


def _head_nll(x, final_ln, head, labels, *, eps, heads):
    """Equation 6: the cross-entropy [B, T, P] of every head at every
    position."""
    table = _f32(head)
    labels = head_labels(labels, heads)

    def nll(block):
        y, lab = block
        logits = (y @ table).reshape(y.shape[:2] + (heads, -1))
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, lab[..., None], -1)[..., 0]

    y = _rms(x, final_ln, eps)
    T = y.shape[1]
    return jnp.concatenate([
        nll((y[:, at:at + _BLOCK], labels[:, at:at + _BLOCK]))
        for at in range(0, T, _BLOCK)], axis=1)


def forward(params, tokens, labels, model):
    """Every position's cross-entropy under each head [B, T, P]. The
    layers are one jitted function called once a layer, and the head
    another: beside a job's parameters and optimizer state the chip has
    no room for the float32 activations of four layers at once (called
    under an outer ``jax.jit`` they are inlined, and nothing changes but
    that)."""
    run = jax.jit(jax.checkpoint(functools.partial(layer, model=model)))
    head = jax.jit(jax.checkpoint(functools.partial(
        _head_nll, eps=model["rms_norm_eps"],
        heads=model["num_pred_heads"])))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        for at in range(model["num_hidden_layers"]):
            x = run(x, layer_leaves(params, at))
        return head(x, params["final_ln"], params["head"], labels)


def step_readings(params, tokens, labels, model):
    """What one training step is held to: the loss and every position's
    cross-entropy under each head."""
    nll = forward(params, tokens, labels, model)
    return dict(loss=jnp.mean(nll), nll=nll)


def loss_and_grad(params, tokens, labels, model):
    """(loss, its gradient by every leaf, in the leaf's own dtype)."""
    return jax.value_and_grad(lambda weights: jnp.mean(
        forward(weights, tokens, labels, model)))(params)
