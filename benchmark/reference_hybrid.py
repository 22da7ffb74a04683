"""Plain float32 reference of the Granite 4.0-H decoder (``model_type``
``granitemoehybrid`` with no experts: ``ibm-granite/granite-4.0-h-micro``
``config.json``; Mamba-2 is Dao & Gu, arXiv:2405.21060): every token's
cross-entropy, the loss, and ``jax.grad`` of it by every parameter leaf.

Straightforward ``jax.numpy`` at the chip's highest matmul precision, one
sequence at a time: no kernel, no sharding, no chunked scan, nothing of
``horovod_tpu`` but its parameter *values* in its layouts. The state-space
layer is the *recurrence* over time (``lax.scan`` over T with the state
[H, P, N]), so the program's chunking, decay sums and carried state are
held to something that has none of them.

The model, for one sequence of tokens and ``x = 12 * embed[tokens]`` [T, d]
(``embedding_multiplier``), layer by layer as ``layer_types`` says, no
biases but the convolution's, ``RMSNorm(v; g) = v * rsqrt(mean(v^2) + eps)
* g``, ``r`` the ``residual_multiplier``:

1. Mixer on ``h = RMSNorm(x; g1)``, then ``x = x + r * mixer(h)``.
   * ``attention``: ``q = h Wq`` [T, H, Dh], ``k, v = h Wkv`` [T, Hkv, Dh],
     each key/value head repeated H / Hkv times, no rotation and no
     position table (``nope``); ``softmax(q k^T * m + causal mask) v``
     with ``m`` the ``attention_multiplier`` (not Dh^-1/2); ``concat(heads)
     Wo``.
   * ``mamba``: ``z, x' = h Wzx`` [T, H, P] each, ``BC = h Wbc`` [T, 2, N],
     ``dt = h Wdt`` [T, H]; ``x' = silu(conv(x') + b)`` and ``BC =
     silu(conv(BC) + b)``, ``conv`` causal, depthwise, width k (``y_t =
     sum_j w_j u_{t-k+1+j}``); ``B, C = BC``; ``dt = softplus(dt +
     dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1}
     + dt_t x'_t (x) B_t``, ``y_t = S_t C_t + D x'_t``; ``y = RMSNorm(y *
     silu(z); g)`` over all H * P channels; ``y Wout``.
2. ``u = RMSNorm(x; g2)``; ``x = x + r * (silu(u Wg) * (u Wu)) Wd``.
3. After the last layer ``logits = RMSNorm(x; gf) embed^T / logits_scaling``
   (the head is the embedding table).
4. Loss = mean token cross-entropy.

Departures from the published model (``configs/granite-4.0-h-micro.json``
under ``assumed``): weights are the program's seeded ``init_params`` (with
Mamba-2's defaults for ``A_log``, ``dt_bias`` and ``D``), not the released
ones; the in-projection is held as three matrices and the convolution as
two, a layout and not the mathematics; bf16 AdamW is the program's, not
the reference's, which has no optimizer.

Layouts (``models/transformer.py``'s; the two leading axes [stages, layers
of the leaf's kind a stage] are read as one axis): ``embed`` [V, d];
``ln1``, ``ln2`` [S, L, d]; ``wq`` [S, La, d, H, Dh]; ``wkv`` [S, La, d, 2,
Hkv, Dh]; ``wo`` [S, La, H, Dh, d]; ``m_wzx`` [S, Lm, d, 2, H, P]; ``m_wbc``
[S, Lm, d, 2, N]; ``m_wdt`` [S, Lm, d, H]; ``m_conv_x`` [S, Lm, k, H, P],
``m_conv_xb`` [S, Lm, H, P]; ``m_conv_bc`` [S, Lm, k, 2, N], ``m_conv_bcb``
[S, Lm, 2, N]; ``m_dt_bias``, ``m_A_log``, ``m_D`` [S, Lm, H]; ``m_g`` [S,
Lm, H, P]; ``m_wo`` [S, Lm, H, P, d]; ``wgu`` [S, L, d, 2, F]; ``w2`` [S, L,
F, d]; ``final_ln`` [d].

Attention scores and logits are formed a block of rows at a time (each row
whole: the softmax is the plain one), and each layer is under
``jax.checkpoint``: that changes what is held at once, not what is
computed, and lets 8,192 tokens at published widths fit beside the program.
"""

import jax
import jax.numpy as jnp
from jax import lax

_ATTENTION = ("wq", "wkv", "wo")
_ROWS = 512  # rows of scores or logits formed at once


def _kind_of(name):
    """The kind of layer a stacked leaf belongs to; None: every layer."""
    if name.startswith("m_"):
        return "mamba"
    return "attention" if name in _ATTENTION else None


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(v, g, eps):
    return v * lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                         + eps) * _f32(g)


def _row_blocks(fn, *rows):
    """``fn`` over blocks of ``_ROWS`` leading rows of ``rows``, the
    results joined again."""
    T = rows[0].shape[0]
    size = _ROWS if T % _ROWS == 0 else T
    cut = lambda a: a.reshape((T // size, size) + a.shape[1:])
    out = lax.map(lambda args: fn(*args), tuple(cut(a) for a in rows))
    return out.reshape((T,) + out.shape[2:])


def _conv(u, w, b):
    """Causal depthwise convolution of u [T, ...] by w [k, ...]."""
    k, T = w.shape[0], u.shape[0]
    before = jnp.zeros((k - 1,) + u.shape[1:], u.dtype)
    padded = jnp.concatenate([before, u])
    return sum(padded[j:j + T] * w[j] for j in range(k)) + b


def ssd_recurrence(x, dt, A, B, C, D):
    """``y`` [T, H, P] of ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t + D x_t`` by a scan over the T tokens; x [T, H, P],
    dt [T, H], A, D [H], B, C [T, N], all float32."""
    def step(S, args):
        x_t, dt_t, B_t, C_t = args
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t)
        return S, jnp.einsum("hpn,n->hp", S, C_t) + D[:, None] * x_t

    S0 = jnp.zeros(x.shape[1:] + B.shape[1:], x.dtype)
    return lax.scan(step, S0, (x, dt, B, C))[1]


def _attention(h, lp, multiplier):
    T = h.shape[0]
    wq, wkv = _f32(lp["wq"]), _f32(lp["wkv"])
    q = jnp.einsum("td,dhk->thk", h, wq)
    k, v = (jnp.einsum("td,dhk->thk", h, wkv[:, c]) for c in range(2))
    repeat = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, repeat, axis=1), jnp.repeat(v, repeat, axis=1)

    def rows(q_rows, at):
        s = jnp.einsum("thk,shk->hts", q_rows, k) * multiplier
        s = jnp.where(at[None, :, None] >= jnp.arange(T)[None, None], s,
                      -jnp.inf)
        return jnp.einsum("hts,shk->thk", jax.nn.softmax(s, -1), v)

    o = _row_blocks(rows, q, jnp.arange(T))
    return jnp.einsum("thk,hkd->td", o, _f32(lp["wo"]))


def _mamba(h, lp, eps):
    zx = jnp.einsum("td,dchp->tchp", h, _f32(lp["m_wzx"]))
    z, x = zx[:, 0], zx[:, 1]
    bc = jnp.einsum("td,dcn->tcn", h, _f32(lp["m_wbc"]))
    dt = h @ _f32(lp["m_wdt"])
    x = jax.nn.silu(_conv(x, _f32(lp["m_conv_x"]), _f32(lp["m_conv_xb"])))
    bc = jax.nn.silu(_conv(bc, _f32(lp["m_conv_bc"]),
                           _f32(lp["m_conv_bcb"])))
    dt = jax.nn.softplus(dt + _f32(lp["m_dt_bias"]))
    y = ssd_recurrence(x, dt, -jnp.exp(_f32(lp["m_A_log"])), bc[:, 0],
                       bc[:, 1], _f32(lp["m_D"]))
    T = y.shape[0]
    y = _rmsnorm((y * jax.nn.silu(z)).reshape(T, -1),
                 _f32(lp["m_g"]).reshape(-1), eps)
    return y @ _f32(lp["m_wo"]).reshape(y.shape[1], -1)


def sequence_nll(params, tokens, labels, model):
    """Cross-entropy of each token [T] of one sequence ``tokens`` [T].
    ``model``: the published ``layer_types``, ``rms_norm_eps`` and the
    four multipliers (``embedding_multiplier``, ``residual_multiplier``,
    ``attention_multiplier``, ``logits_scaling``)."""
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    stacks = {k: v.reshape((-1,) + v.shape[2:]) for k, v in params.items()
              if k not in ("embed", "final_ln")}

    @jax.checkpoint
    def layer(x, lp):
        h = _rmsnorm(x, lp["ln1"], eps)
        if "wq" in lp:
            x = x + r * _attention(h, lp, model["attention_multiplier"])
        else:
            x = x + r * _mamba(h, lp, eps)
        u = _rmsnorm(x, lp["ln2"], eps)
        wgu = _f32(lp["wgu"])
        hidden = jax.nn.silu(u @ wgu[:, 0]) * (u @ wgu[:, 1])
        return x + r * (hidden @ _f32(lp["w2"]))

    embed = _f32(params["embed"])
    x = model["embedding_multiplier"] * embed[tokens]
    seen = {"attention": 0, "mamba": 0}
    for at, kind in enumerate(model["layer_types"]):
        lp = {k: v[seen[kind] if _kind_of(k) else at]
              for k, v in stacks.items() if _kind_of(k) in (None, kind)}
        seen[kind] += 1
        x = layer(x, lp)
    y = _rmsnorm(x, params["final_ln"], eps)

    def rows(y_rows, labs):
        logp = jax.nn.log_softmax(
            y_rows @ embed.T / model["logits_scaling"], -1)
        return -jnp.take_along_axis(logp, labs[:, None], -1)[:, 0]

    return _row_blocks(jax.checkpoint(rows), y, labels)


def token_nll(params, tokens, labels, model):
    """Every token's cross-entropy [B, T] over ``tokens`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        return lax.map(lambda args: sequence_nll(params, *args, model),
                       (tokens, labels))


def decoder_hybrid_loss(params, tokens, labels, model):
    """The training loss over ``tokens`` [B, T] (4 above): the mean."""
    return jnp.mean(token_nll(params, tokens, labels, model))


def decoder_hybrid_loss_and_grad(params, tokens, labels, model):
    """(loss, gradient of the loss by every parameter leaf, in the leaf's
    own dtype)."""
    return jax.value_and_grad(decoder_hybrid_loss)(params, tokens, labels,
                                                   model)
