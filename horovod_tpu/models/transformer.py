"""Flagship sharded transformer: dp x pp x sp x tp (+ ep on dp).

This model is the parallelism showcase the TPU build adds beyond the
reference's DP-only surface (SURVEY §2.5): every mesh axis of
``horovod_tpu.parallel.mesh`` is exercised in one training step —

- **dp**: batch sharded; gradients reduced across dp by the autodiff
  transpose of the replicated-parameter broadcast (the same math
  ``hvd.DistributedOptimizer`` performs explicitly).
- **pp**: decoder layers split into stages, GPipe schedule via
  ``parallel.pipeline.spmd_pipeline`` (params sharded over ``pp``).
- **sp**: sequence/context parallelism — the token axis is sharded and
  attention runs as ring attention (``parallel.ring_attention``) or
  all-to-all Ulysses-style re-sharding (``parallel.ulysses``), selected
  by ``TransformerConfig.sp_strategy``.
- **tp**: Megatron-style tensor parallelism — attention heads and MLP
  hidden dim sharded over ``tp``, partial outputs psum'd.
- **ep**: MoE experts sharded over the dp axis (``parallel.moe``):
  dropless top-k routing, sort-by-expert dispatch, grouped matmuls.

The stack of layers is data. ``TransformerConfig.layer_types`` names each
layer's mixer, a key of the one table ``MIXERS``. A kind's entry
(``Mixer``) is all the module knows of it: the group of stacks it reads,
their ``PartitionSpec``s and how they are drawn, what it checks of a
configuration and refuses of a layout, its mixer function; a new kind is
an entry. The kinds: ``"attention"`` (softmax attention, above, with the
model's one ``attention_window`` and ``rope`` switch),
``"sliding_attention"`` (over ``sliding_window`` tokens, rotated) and
``"full_attention"`` (causal over everything, no positions), three
entries over one function and one set of leaves; ``"mamba"`` (Mamba-2,
``_mamba_mixer``), ``"latent_attention"`` (``_latent_mixer``),
``"cca"`` (``_cca_mixer``), ``"eva"`` (``_eva_mixer``) and ``"kda"``
(``_kda_mixer``), each with leaves of its own. Each layer ends
in a feed-forward block that is data too: the dense MLP, or with
``use_moe`` the expert layer in all but the ``num_dense_layers`` leading
layers. Parameters are stacked per group (each group of mixers, the dense
blocks, the expert blocks, and what every layer has), and a stage scans
each maximal run of one (mixer, feed-forward) pair over its slice of the
stacks (``_make_stage_fn``); a pattern of one pair is one scan. What
rides beside the activations from stage to stage has names (``Carry``:
the segment ids of packed documents, the router statistics, the router's
state); a member a model does not have is None.

The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; HF
``GraniteMoeHybridMambaLayer``), for one sequence of normed hidden states
``h`` [T, d], H heads of P channels, state N, one group::

    [z | xBC | dt] = h W_in                 widths H P | H P + 2 N | H, no bias
    xBC = silu(conv1d_causal_depthwise(xBC, k) + b)
    x [T, H, P], B [T, N], C [T, N] = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)                  per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    y = rmsnorm(y * silu(z)) * g       over all H P channels, gate first
    out = y W_out

``dt``, ``A``, the decays, their sums, the state and the norm are float32;
the matmuls take operands in the model's type and accumulate in float32
(``ops/ssd.py`` has the scan in its chunked form). ``W_in`` is held in
three parts, ``m_wzx`` [d, 2, H, P], ``m_wbc`` [d, 2, N] and ``m_wdt``
[d, H], and the convolution in two, so that the heads shard over ``tp``
while ``B`` and ``C`` stay whole on every member.

Latent attention (DeepSeek-V2/V3's MLA, arXiv:2412.19437 section 2.1) on
normed ``h`` [T, d], H heads of ``d_head`` = ``qk_nope_head_dim`` +
``qk_rope_head_dim`` channels, ``rms`` an RMSNorm with its own weight::

    c_q = rms(h W_qa) [q_lora_rank];  q_i = c_q W_qb[i] = [q_i^nope ; q_i^rope]
    [c_kv | k^rope] = h W_kva         widths kv_lora_rank | qk_rope_head_dim
    [k_i^nope | v_i] = rms(c_kv) W_kvb[i]      widths qk_nope_head_dim | d_head
    k_i = [k_i^nope ; rope(k^rope)],  q_i = [q_i^nope ; rope(q_i^rope)]
    out = concat_i softmax_causal(q_i k_i^T / sqrt(d_head)) v_i  W_o

``k^rope`` is one rotated head that all H query heads read; the rotation
(rotate-half) takes the *last* ``qk_rope_head_dim`` channels of a head
whole. Queries, keys and values enter the flash kernels at the one width
``d_head``. The two low-rank norms are float32. The heads (``l_wqb``,
``l_wkvb``, ``l_wo``) shard over ``tp``; the down-projections and their
norms are whole on every member. Four switches: ``q_lora_rank`` 0 makes
the query one full-rank matrix, ``q_i = h W_q[i]`` (``l_wq``, no latent
and no norm); ``v_head_dim`` gives the values a width of their own,
narrower than a key's ``qk_nope_head_dim + qk_rope_head_dim`` (they ride
zeros up to it into the kernels, whose softmax scale is the keys' width's,
and the zeros' columns of the result are dropped); ``qk_norm`` "head" is a
learned RMSNorm over a head's whole query and whole key (its own channels
beside the shared ones) before the rotation, one weight vector each for
all heads (``l_gq``, ``l_gk``); ``attn_gate`` "head" multiplies a head's
result by ``sigmoid(h W_gate)_i``, one gate a head (``l_wgate`` [d, H]).

Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692 section 3) on
normed ``h`` [T, d], H heads whose keys and values have ``kda_head_dim``
= K channels, ``conv`` a causal depth-wise convolution of ``kda_conv``
taps (zeros before the first token, no bias)::

    q~, k~, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
    q = K^-1/2 q~ / |q~|;  k = k~ / |k~|        over a head's channels
    g = floor * sigmoid(exp(A_h) (h W_f + b))     [H, K], in (floor, 0)
    beta = sigmoid(h W_beta)                      [H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                               S_0 = 0, [K, K] a head
    out = concat_i(rms(o_i) g_o * sigmoid(h W_g)_i) W_o

``floor`` is ``kda_gate_floor`` (-5: the decay ``exp(g)`` of a channel
lies in (e^-5, 1)), ``A_h`` a scalar a head, ``b`` a bias, ``g_o`` one
weight [K] for all heads; no rotation. The norms, the decay and what it is
made of, beta, both sigmoids and the scan's state are float32 (``ops/
kda.py`` has the recurrence in its chunked form, ``kda_chunk`` tokens a
chunk). Leaves ``k_*``; heads over ``tp``.

Multi-token prediction (the same paper, section 2.2), one module after
the stack with ``n_mtp_modules``: on the stack's output ``x`` before the
final norm and the tokens' *labels* ``t_{i+1}``::

    g_i = [rms_h(x_i) ; rms_e(E[t_{i+1}])] W_eh           [2 d] -> [d]
    z = layer(g)        one more layer, own leaves: of the stack's last
                        kind, or of ``mtp_layer_type`` where that is stated
    loss = CE(head(final_ln(x)), t_{i+1})
           + mtp_loss_weight * CE(head(rms_s(z)), t_{i+2})

through the same ``embed`` and ``head``; the second mean is over the
positions that have a ``t_{i+2}`` (all but a sequence's last). The
module's leaves are ``mtp_hnorm``, ``mtp_enorm``, ``mtp_eh``,
``mtp_final_ln`` and its layer's, each the stack's name after ``mtp_``,
with the modules (one) as their leading dimension.

Compressed convolutional attention (CCA, arXiv:2510.04476 section 3) on
normed ``h`` [T, d], ``Hq`` query heads over ``Hkv`` key/value heads of
``Dh`` channels, group ``g = Hq / Hkv``, no biases; the whole attention is
inside the compressed space ``Hq Dh`` < d::

    q~ = h W_q [T, Hq, Dh];  k~ = h W_k [T, Hkv, Dh]
    v  = h W_v [T, Hkv, Dh], the upper half of its heads taken from the
         token before (zero at t = 0): the values' shift
    conv0 (width cca_time0): causal, depthwise: y_t[c] = sum_j w0[j, c] x_{t-(K0-1)+j}[c]
    conv1 (width cca_time1): causal, by head:   y_t[i] = sum_j x_{t-(K1-1)+j}[i] W1[j, i]
    q^ = conv1(conv0(q~)),  k^ = conv1(conv0(k~))          own filters each
    q = q^ + (q~ + repeat_g(k~)) / 2;  k = k^ + (mean_g(q~) + k~) / 2
    q = q * rsqrt(mean(q^2) + eps);  k = beta_j k * rsqrt(mean(k^2) + eps)
    rotate the first ``partial_rotary_factor`` of a head's channels of q, k
    out = concat_i softmax_causal(q_i k_{i // g}^T / sqrt(Dh)) v_{i // g}  W_o

The norm (``sqrt(Dh) x / |x|`` with ``eps`` under the root: ``_rmsnorm``
over a head's channels) and the temperature ``beta`` [Hkv] are float32.
Queries, keys and values enter the flash kernels at their own head
counts. Leaves ``c_*``; heads over ``tp``.

The ZAYA router (arXiv:2511.17127) of an expert layer ``l`` with
``router_hidden`` = R > 0, on the block's normed ``h``, float32 throughout::

    r_l = h W_down + gamma_l r_{l-1}        [T, R]; r_{-1} = 0; on to layer l + 1
    s = gelu(gelu(rms(r_l) W_1) W_2) W_3    [T, E]
    p = softmax(s);  picks = top_k(p + bias);  weights = p[picks]

``r_l`` is ``Carry.state``, beside the activations in every scan. A
sigmoid router with ``moe_n_group`` > 1 picks its ``moe_top_k`` among the
experts of the ``moe_topk_group`` best groups of consecutive experts, a
group scored by the sum of its two largest ``score + bias``
(``parallel.moe.moe_layer``). With
``residual_scales`` a block joins the stream as ``a * x + b + c * block(
norm(x))`` with learned float32 ``a``, ``b``, ``c`` [d] (one, zero, one at
the start), leaves ``res1`` and ``res2`` [3, d].

EVA attention (Zheng et al., arXiv:2302.04542, the deterministic form
EvaByte runs) on normed ``h`` [T, d], H heads of D channels, windows of
``eva_window`` = W positions and chunks of ``eva_chunk`` = C, two learned
vectors ``mu``, ``phi`` [D] a head::

    q, k, v = h W_q, h W_k, h W_v;  q, k rotated over the whole head
    k~_c = sum_{m in chunk c} softmax_m(mu . k_m) k_m        (keys rotated)
    v~_c = sum_{m in chunk c} softmax_m(phi . k_m) v_m
    E_i = {m : m // W = i // W, m <= i};  R_i = {c : c < (W / C) (i // W)}
    o_i = (sum_E e^{s_im} v_m + sum_R e^{r_ic} v~_c) / (sum_E e^{s_im} + sum_R e^{r_ic})
    s_im = q_i . k_m / sqrt(D),  r_ic = q_i . k~_c / sqrt(D);  out = concat(o) W_o

A query sees its own window exactly and a summary a chunk of every earlier
window, under one softmax (``ops/eva_attention.py``: two calls of the flash
kernels, the second under the block-causal rule, joined by the
online-softmax combine). The poolings, the softmax and its statistics are
float32. With C = 1 the summaries are the keys and values themselves and
with W >= T there is none: either way the layer is causal softmax
attention. Leaves ``e_*``; heads over ``tp``, ``mu`` and ``phi`` with them.

With ``float32_stream`` the residual stream (``Carry.x``, a rematerialized
layer's kept input) is float32 while every block computes in the model's
type: a norm reads the stream and writes the model's type, a block's
result joins the stream in float32. ``norm_unit_offset`` scales a norm by
``1 + g`` (g zero at the start). With ``n_pred_heads`` = P > 1 the head's
one matrix [d, P V] gives P predictions a position, head ``j`` of the
token ``1 + j`` ahead (labels shifted by ``j`` more, as the labels are by
one), and the loss is the mean cross-entropy over heads and positions.

With ``head_block`` the head and the loss run by blocks of that many
tokens (``block_nll``): a block's float32 logits are formed once, in the
forward pass, which where the loss is differentiated also makes the hidden
states' and the table's gradients from them, under the weight the loss
gives each token (the mean's ``1 / N``, an argument of ``block_nll``);
the backward pass multiplies both by the loss's cotangent. No [b, t, V]
array exists in either pass.

Pure-jax pytree params (no flax) so shard_map in_specs map 1:1 onto leaves.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from ..common import metrics as _metrics

with _metrics.span("import:horovod_tpu.models.transformer"):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.ad_checkpoint import checkpoint_name
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..common.compat import axis_size as _axis_size
    from ..common.compat import shard_map as _compat_shard_map
    from ..ops.kda import kda_chunked
    from ..ops.ssd import ssd_chunked
    from ..parallel.moe import moe_layer
    from ..parallel.pipeline import spmd_pipeline
    from ..parallel.ulysses import context_parallel_attention


# What a layer names with ``checkpoint_name``, so that a rematerialized
# layer can keep it (``TransformerConfig.remat_keeps``): the Mamba
# in-projection's z and x and the scan's output; an attention mixer's Q,
# K/V and gate projections, its output projection, and the flash kernels'
# output and row statistics (ops/pallas_attention.py); the gate-up product
# of a gated MLP or shared expert; a latent mixer's two down-projections
# (the query latent; the key/value latent with the shared rotated key),
# its up-projections under the attention mixer's ``attn_q``, ``attn_kv``;
# a CCA mixer's two latents (the queries'; the keys' with the shifted
# values) and its convolved queries and keys, what it hands the kernels
# again under ``attn_q``, ``attn_kv``; an EVA mixer's rotated queries and
# its rotated keys with the values under the same two, its joint output
# and row statistics under ``flash_out``, ``flash_lse``; a KDA mixer's
# query, key and value projection, its two gates' projections and the
# scan's output.
REMAT_NAMES = ("mamba_zx", "ssd_out", "attn_q", "attn_kv", "attn_gate",
               "attn_proj", "flash_out", "flash_lse", "mlp_gu", "mla_cq",
               "mla_ckv", "cca_q", "cca_kv", "cca_conv", "kda_qkv",
               "kda_gates", "kda_out")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    d_head: int = 16
    d_ff: int = 256
    n_layers: int = 4
    max_seq: int = 64
    # The feed-forward block is the dropless expert layer
    # (parallel/moe.py) in every layer but the ``num_dense_layers``
    # leading ones, which keep the dense MLP (``d_ff``, ``gated_mlp``).
    use_moe: bool = False
    num_dense_layers: int = 0
    # Experts the router scores, and of those the ones this program
    # holds: ``n_experts_held`` from ``first_expert_held`` (None: all of
    # them). A program that holds a share computes that share's part of
    # the layer's result; what the other experts would add is left out.
    n_experts: int = 4
    n_experts_held: Optional[int] = None
    first_expert_held: int = 0
    d_expert: int = 128
    moe_top_k: int = 1  # experts a token; nothing is dropped
    # "softmax": the top-k router probabilities. "sigmoid": scores
    # sigmoid(logits), times ``route_scale`` (``norm_topk_prob`` divides
    # by the picked scores' sum first).
    moe_score_func: str = "softmax"
    route_scale: float = 1.0
    # Group-limited selection of a sigmoid router (DeepSeek-V3's): the
    # experts in ``moe_n_group`` groups of consecutive ones, a group scored
    # by the sum of its two largest ``score + bias``, and the top-k taken
    # among the ``moe_topk_group`` best groups' experts. One group: plain.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Gated MLPs of width ``n_shared_experts * d_expert`` that every
    # token passes beside its routed experts.
    n_shared_experts: int = 0
    # Aux-loss-free balancing: > 0 gives each expert layer an
    # ``expert_bias`` [E] that is added to the scores for the top-k
    # *selection* only. It is no trained parameter: the train step moves
    # it by ``rate * sign(mean load - load)`` (centred) from the tokens
    # each expert got, and the optimizer never sees it.
    expert_bias_rate: float = 0.0
    # Divide a token's top-k router probabilities by their sum (a
    # published config's ``norm_topk_prob``); False takes them as they are.
    norm_topk_prob: bool = False
    # Weights of the router's two extra loss terms, each a mean over the
    # MoE layers: load balance (``router_aux_loss_coef``) and the squared
    # log-sum-exp of the router logits (z-loss).
    router_aux_loss_coef: float = 0.0
    router_z_loss_coef: float = 0.0
    # "layernorm": scale-only LayerNorm, eps 1e-5. "rmsnorm":
    # v * rsqrt(mean(v^2) + norm_eps) * g, in float32.
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    # RMSNorm on the projected queries and keys before they are rotated.
    # True: over the whole projected vector (all heads of every tp
    # member), one weight a channel. "head": over each head's ``d_head``
    # channels, one weight vector [d_head] for all heads.
    qk_norm: Any = False
    # The kernels' output times ``sigmoid(h W_gate)`` (W_gate [d, H, Dh])
    # before the output projection. "head": one gate a head (W_gate [d,
    # H]), through a latent layer, which takes no other.
    attn_gate: Any = False
    # Each branch is normed again before it joins the residual stream:
    # ``x + norm(mixer(norm(x)))``, ``x + norm(ffn(norm(x)))``.
    post_norms: bool = False
    dtype: Any = jnp.float32
    # Sequence-parallel attention strategy over the sp axis: "ring"
    # (K/V rotation, no head constraint), "ulysses" (all-to-all head
    # re-shard, needs (n_heads/tp) % sp == 0), or "auto"
    # (parallel/ulysses.py).
    sp_strategy: str = "ring"
    # Sliding-window attention (Mistral-style SWA) in the layers of kind
    # "attention": each token attends to itself plus the
    # `attention_window - 1` preceding tokens (receptive field =
    # attention_window; mask q_pos - k_pos < W). None = full causal.
    # Out-of-window K tiles are culled in the kernels.
    attention_window: Optional[int] = None
    # The same for the layers of kind "sliding_attention", which must
    # state one; "full_attention" layers have none.
    sliding_window: Optional[int] = None
    # Rematerialize each decoder layer in the backward pass
    # (jax.checkpoint): activations are recomputed instead of saved, so
    # activation HBM drops from O(n_layers) to O(1) layers — the
    # standard trade that lets long sequences fit, at ~1/3 extra FLOPs.
    # A layer keeps what ``remat_keeps`` names all the same, of
    # ``REMAT_NAMES`` (None: a Mamba layer's ``_REMAT_KEEPS``).
    remat: bool = False
    remat_keeps: Optional[Tuple[str, ...]] = None
    # Grouped-query attention (Llama/Mistral-style): n_kv_heads < n_heads
    # shares each K/V head across n_heads/n_kv_heads query heads (KV
    # params cut by that factor). K/V cross the sp fabric and enter the
    # kernels at their own head count: a group's query heads read one K/V
    # head through the kernels' index maps, and the dK/dV pass sums the
    # group in VMEM (ops/pallas_attention.py), so no copy of K, V or
    # their gradients at n_heads exists. None = multi-head (= n_heads).
    n_kv_heads: Optional[int] = None
    # Rotary position embeddings in the layers of kind "attention",
    # instead of the learned position table ("sliding_attention" layers
    # always rotate, "full_attention" layers never). Positions are GLOBAL
    # (sp-sharded ranks offset by their shard), so RoPE composes with
    # sequence parallelism.
    rope: bool = False
    rope_theta: float = 10000.0
    # The learned position table of a model that does not rotate. False
    # with ``rope`` False: no positional signal at all (NoPE).
    pos_table: bool = True
    # Each layer's mixer, one of LAYER_KINDS, in order; None = every
    # layer is the first of them, "attention". A published ``layer_types``.
    layer_types: Optional[Tuple[str, ...]] = None
    # The Mamba-2 mixer: heads of ``mamba_d_head`` channels (their product
    # is the inner width), state size, causal depthwise convolution
    # width, and the chunk of the scan (ops/ssd.py). One group of B, C.
    mamba_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    # The latent mixer (the module's docstring): the two ranks
    # (``q_lora_rank`` 0: the query's one full-rank matrix, no latent),
    # and a head's rotated and unrotated widths, which add up to a query's
    # and a key's width; ``v_head_dim`` the value's (0: ``d_head``, which
    # the two then add up to). Rotated with ``rope_theta``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    # The KDA mixer (the module's docstring): ``n_heads`` heads whose keys
    # and values have ``kda_head_dim`` channels, the taps of its three
    # causal depthwise convolutions, the lower bound of a token's log
    # decay, and the chunk of the scan (ops/kda.py).
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_floor: float = -5.0
    kda_chunk: int = 64
    # The CCA mixer (the module's docstring): the widths of its two
    # causal convolutions over the sequence, and the share of a head's
    # channels, from the first, that is rotated (with ``rope_theta``).
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 1.0
    # The EVA mixer (the module's docstring): positions a window, whose
    # keys a query sees exactly, and positions a chunk, each summarised
    # as one key and value for the queries of later windows.
    eva_window: int = 0
    eva_chunk: int = 0
    # The expert layers' router is the ZAYA router's MLP over a state of
    # this width that crosses layers (the module's docstring); 0: the
    # linear router.
    router_hidden: int = 0
    # Learned scales and offsets where a block joins the residual stream
    # (the module's docstring).
    residual_scales: bool = False
    # Tokens a block of the head and the loss (``block_nll``); None: the
    # head's logits whole.
    head_block: Optional[int] = None
    # Tokens a block of the dense MLP: the blocks run one after another
    # (a ``lax.scan``), each rematerialized in the backward pass, so that
    # the MLP's [t, d_ff] arrays exist a block at a time in either pass
    # (under ``remat`` at no FLOPs more: the layer's second forward then
    # keeps a block's input alone and its matmuls are dead code). A
    # weight's gradient is the blocks' terms summed in the model's type.
    # None: the sequence whole.
    mlp_block: Optional[int] = None
    # Predictions a position from the head's one matrix [d, P V]: head j
    # of the token 1 + j ahead (the module's docstring).
    n_pred_heads: int = 1
    # RMSNorm scales by ``1 + g``; the residual stream is float32
    # whatever ``dtype`` the blocks compute in (the module's docstring).
    norm_unit_offset: bool = False
    float32_stream: bool = False
    # Multi-token-prediction modules after the stack (0 or 1; the
    # module's docstring) and the weight of their cross-entropy.
    n_mtp_modules: int = 0
    mtp_loss_weight: float = 0.3
    # The kind of the module's layer; None: the stack's last.
    mtp_layer_type: Optional[str] = None
    # Dense feed-forward W_d (silu(h W_g) * h W_u) of width d_ff instead
    # of W_2 gelu(h W_1).
    gated_mlp: bool = False
    # The head multiplies by the embedding table itself: one parameter,
    # its gradient the sum of both ends.
    tie_embeddings: bool = False
    # Granite's four multipliers: on the embedded tokens, on each
    # branch before it joins the residual stream, a divisor of the
    # logits, and the softmax scale in place of d_head^-1/2 (None).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None

    def __post_init__(self):
        if self.n_kv_heads is not None:
            if self.n_kv_heads < 1:
                raise ValueError(
                    f"n_kv_heads must be >= 1, got {self.n_kv_heads}")
            if self.n_heads % self.n_kv_heads != 0:
                raise ValueError(
                    f"n_heads ({self.n_heads}) must divide by n_kv_heads "
                    f"({self.n_kv_heads})")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', got "
                             f"{self.norm!r}")
        if self.rope and self.d_head % 2 != 0:
            raise ValueError(f"rope needs an even d_head, got "
                             f"{self.d_head}")
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            object.__setattr__(self, "layer_types", kinds)
            if len(kinds) != self.n_layers or set(kinds) - set(MIXERS):
                raise ValueError(
                    f"layer_types must name {self.n_layers} layers, each "
                    f"one of {tuple(MIXERS)}; got {kinds}")
            for kind in dict.fromkeys(kinds):
                MIXERS[kind].check(self)
        if self.mtp_layer_type is not None:
            if self.mtp_layer_type not in MIXERS or not self.n_mtp_modules:
                raise ValueError(
                    f"mtp_layer_type names the kind of a multi-token-"
                    f"prediction module's layer (n_mtp_modules), one of "
                    f"{tuple(MIXERS)}; got {self.mtp_layer_type!r}")
            MIXERS[self.mtp_layer_type].check(self)
        if self.n_mtp_modules not in (0, 1):
            raise ValueError(
                f"n_mtp_modules must be 0 or 1, got {self.n_mtp_modules}: "
                f"a chain of multi-token-prediction modules is not built")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm must be False, True or 'head', got "
                             f"{self.qk_norm!r}")
        if self.moe_score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score_func must be 'softmax' or "
                             f"'sigmoid', got {self.moe_score_func!r}")
        if self.attn_gate not in (False, True, "head") or (
                self.attn_gate == "head" and any(
                    MIXERS[kind].group == "attention"
                    for kind in self.mixer_kinds)):
            raise ValueError(
                f"attn_gate must be False, True or 'head', and one gate a "
                f"head is built through latent_attention layers alone; got "
                f"{self.attn_gate!r} with layers {self.mixer_kinds}")
        if self.moe_n_group != 1 and (
                self.moe_score_func != "sigmoid" or self.router_hidden
                or self.n_experts % self.moe_n_group
                or not 1 <= self.moe_topk_group <= self.moe_n_group
                or self.moe_top_k > self.moe_topk_group
                * (self.n_experts // self.moe_n_group)):
            raise ValueError(
                f"moe_n_group ({self.moe_n_group}) groups of a sigmoid "
                f"router's {self.n_experts} experts, of which "
                f"moe_topk_group ({self.moe_topk_group}) must hold the "
                f"{self.moe_top_k} a token")
        if not 0 <= self.num_dense_layers <= self.n_layers or (
                self.num_dense_layers and not self.use_moe):
            raise ValueError(
                f"num_dense_layers ({self.num_dense_layers}) counts the "
                f"leading dense layers of a use_moe model of "
                f"{self.n_layers} layers")
        if self.n_experts_held is not None and not (
                0 <= self.first_expert_held
                and 1 <= self.n_experts_held
                and self.first_expert_held + self.n_experts_held
                <= self.n_experts):
            raise ValueError(
                f"the held experts [{self.first_expert_held}, "
                f"{self.first_expert_held} + {self.n_experts_held}) are "
                f"not among the {self.n_experts} the router scores")
        if self.expert_bias_rate and (
                not self.use_moe or (self.moe_score_func != "sigmoid"
                                     and not self.router_hidden)):
            raise ValueError("expert_bias_rate moves the selection bias of "
                             "a sigmoid router or of the router's MLP "
                             "(use_moe, moe_score_func, router_hidden)")
        if self.router_hidden and (not self.use_moe or self.n_mtp_modules
                                   or self.moe_score_func != "softmax"):
            raise ValueError(
                "router_hidden is the softmax MLP router of a use_moe "
                "model's expert layers; its state through a "
                "multi-token-prediction module is not built")
        if self.head_block is not None and (self.head_block < 1
                                            or self.n_mtp_modules):
            raise ValueError(
                "head_block counts the tokens of a block of the head and "
                "the loss; a multi-token-prediction module's head by "
                "blocks is not built")
        if self.mlp_block is not None and self.mlp_block < 1:
            raise ValueError("mlp_block counts the tokens of a block of "
                             "the dense MLP")
        if self.n_pred_heads < 1 or (self.n_pred_heads > 1 and (
                self.head_block is not None or self.tie_embeddings
                or self.n_mtp_modules)):
            raise ValueError(
                "n_pred_heads counts the predictions a position of an "
                "untied head; through a tied head, the head by blocks or a "
                "multi-token-prediction module they are not built")
        if (self.norm_unit_offset or self.float32_stream) and (
                self.norm != "rmsnorm" or self.residual_scales):
            raise ValueError(
                "norm_unit_offset and float32_stream are RMSNorm's and the "
                "plain residual's; through LayerNorm or residual_scales "
                "they are not built")
        if self.remat_keeps is not None:
            keeps = tuple(self.remat_keeps)
            object.__setattr__(self, "remat_keeps", keeps)
            if set(keeps) - set(REMAT_NAMES):
                raise ValueError(
                    f"remat_keeps names what a layer writes under "
                    f"checkpoint_name, of {REMAT_NAMES}; got {keeps}")

    @property
    def kv_heads(self) -> int:
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads

    @property
    def experts_held(self) -> int:
        return (self.n_experts if self.n_experts_held is None
                else self.n_experts_held)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, in order."""
        return self.layer_types or (LAYER_KINDS[0],) * self.n_layers

    @property
    def mtp_kind(self) -> str:
        """The mixer of a multi-token-prediction module's layer."""
        return self.mtp_layer_type or self.kinds[-1]

    @property
    def mixer_kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, a multi-token-prediction module's layer
        after the stack's."""
        return self.kinds + (self.mtp_kind,) * self.n_mtp_modules

    @property
    def mtp_layer(self) -> "TransformerConfig":
        """The one-layer model whose layer a multi-token-prediction module
        runs: the stack's last feed-forward block under ``mtp_kind``."""
        return dataclasses.replace(
            self, n_layers=1, layer_types=(self.mtp_kind,), n_mtp_modules=0,
            mtp_layer_type=None,
            num_dense_layers=int(self.use_moe
                                 and self.ffn_kinds[-1] == "mlp"))

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        """Each layer's feed-forward block, "mlp" or "moe", in order."""
        if not self.use_moe:
            return ("mlp",) * self.n_layers
        dense = self.num_dense_layers
        return ("mlp",) * dense + ("moe",) * (self.n_layers - dense)

    def stage_pattern(self, n_stages: int) -> Tuple[Tuple[str, str], ...]:
        """The (mixer, feed-forward) pairs of one pipeline stage. Every
        stage scans the same pattern (the stages are one SPMD program),
        so a stage holds whole periods of it."""
        if self.n_layers % n_stages != 0:
            raise ValueError(f"n_layers ({self.n_layers}) must divide "
                             f"into {n_stages} pipeline stages")
        pattern = tuple(zip(self.kinds, self.ffn_kinds))
        stage = pattern[:self.n_layers // n_stages]
        if stage * n_stages != pattern:
            if self.num_dense_layers:
                raise ValueError(
                    f"{n_stages} pipeline stages over a pattern with "
                    f"{self.num_dense_layers} leading dense layer(s) are "
                    f"not built: the stages are one program and would not "
                    f"hold the same layers")
            raise ValueError(
                f"{n_stages} pipeline stages must each hold whole periods "
                f"of the layer pattern; {self.kinds} does not repeat every "
                f"{len(stage)} layers")
        return stage


# Which layers each stacked leaf has one slice for: every layer (the
# norms), or those of one group (``_leaf_groups``): of mixers, by the
# table; that end in the dense MLP; that end in the expert layer.
_MLP_LEAVES = ("w1", "w2", "wgu")
_ROUTED_LEAVES = ("router", "wg", "wu", "wd", "expert_bias")
# The ZAYA router's own (``router_hidden``), in place of ``router``.
_ROUTER_MLP_LEAVES = ("r_down", "r_gamma", "r_norm", "r_w1", "r_w2", "r_w3")
_MOE_LEAVES = (_ROUTED_LEAVES + ("shared_wgu", "shared_w2")
               + _ROUTER_MLP_LEAVES)
_MODEL_LEAVES = ("embed", "pos", "final_ln", "head")
# A multi-token-prediction module's own leaves; its layer's are the
# stack's names after the same prefix.
_MTP = "mtp_"
_MTP_LEAVES = ("mtp_hnorm", "mtp_enorm", "mtp_eh", "mtp_final_ln")
# Leaves the train step carries that are no trained parameter.
_STATE_LEAVES = ("expert_bias", "mtp_expert_bias")


def _mixer_groups(kinds) -> Dict[str, "Mixer"]:
    """The groups of stacks that layers of ``kinds`` read, each with an
    entry of ``MIXERS`` that reads it (a group's kinds share its leaves)."""
    return {MIXERS[kind].group: MIXERS[kind] for kind in kinds}


def _leaf_groups(cfg: TransformerConfig) -> Dict[str, str]:
    """Stacked leaf -> the group of layers whose stack it is; a leaf every
    layer has is in none."""
    groups = {name: group
              for group, entry in _mixer_groups(cfg.kinds).items()
              for name in entry.specs(cfg)}
    groups.update({name: "mlp" for name in _MLP_LEAVES})
    groups.update({name: "moe" for name in _MOE_LEAVES})
    return groups


def trained(params: Dict) -> Dict:
    """``params`` without the leaves no optimizer may see (the routers'
    ``expert_bias``, the stack's and a multi-token-prediction module's):
    what the optimizer state is made for."""
    return {k: v for k, v in params.items() if k not in _STATE_LEAVES}


def _param_specs(cfg: TransformerConfig) -> Dict[str, P]:
    """PartitionSpecs for every param leaf (leading dims: [S(tage), L(ayers
    of the leaf's kind in the stage)] on per-layer params)."""
    specs = {"embed": P(), "ln1": P("pp"), "ln2": P("pp"), "final_ln": P()}
    if cfg.post_norms:
        specs.update(ln1_post=P("pp"), ln2_post=P("pp"))
    if cfg.residual_scales:
        specs.update(res1=P("pp"), res2=P("pp"))
    if not cfg.tie_embeddings:
        specs["head"] = P()
    if cfg.pos_table and not cfg.rope:
        specs["pos"] = P()
    for entry in _mixer_groups(cfg.kinds).values():
        specs.update(entry.specs(cfg))
    if cfg.n_mtp_modules:
        # The layer's leaves with the modules where the stages were.
        for name, spec in _param_specs(cfg.mtp_layer).items():
            if name not in _MODEL_LEAVES:
                specs[_MTP + name] = P(*spec[1:])
        specs.update({name: P() for name in _MTP_LEAVES})
    if "moe" in cfg.ffn_kinds:
        if cfg.router_hidden:
            specs.update({name: P("pp") for name in _ROUTER_MLP_LEAVES})
        else:
            specs["router"] = P("pp")
        specs.update({k: P("pp", None, "dp") for k in ("wg", "wu", "wd")})
        if cfg.n_shared_experts:  # the dense MLP's layout: width over tp
            specs["shared_wgu"] = P("pp", None, None, None, "tp")
            specs["shared_w2"] = P("pp", None, "tp")
        if cfg.expert_bias_rate:
            specs["expert_bias"] = P("pp")
    if "mlp" in cfg.ffn_kinds:  # width over tp
        specs["w2"] = P("pp", None, "tp")
        if cfg.gated_mlp:
            specs["wgu"] = P("pp", None, None, None, "tp")
        else:
            specs["w1"] = P("pp", None, None, "tp")
    return specs


def init_params(cfg: TransformerConfig, rng, n_stages: int) -> Dict:
    """Global (unsharded) parameter pytree; shard with ``shard_params``."""
    pattern = cfg.stage_pattern(n_stages)
    stage = [MIXERS[mixer].group for mixer, _ in pattern]
    ffns = [ffn for _, ffn in pattern]
    lps = len(pattern)
    d, F = cfg.d_model, cfg.d_ff
    # The model's keys: twelve; the last again five ways for the leaves
    # that came after those were dealt out (the first is the attention
    # gate's); the root folded with a salt for what came later still (1,
    # 3 and 5 in ``MIXERS``, which gives an entry the root; 2 and 4 below).
    ks = jax.random.split(rng, 12)
    _, k_shared_gu, k_shared_2, k_dense_gu, k_dense_2 = \
        jax.random.split(ks[11], 5)
    dt = cfg.dtype

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(dt)

    # A norm's weight starts where it scales by one.
    unit = jnp.zeros if cfg.norm_unit_offset else jnp.ones
    params = {
        "embed": norm(ks[0], (cfg.vocab, d), 0.02),
        "ln1": unit((n_stages, lps, d), jnp.float32),
        "ln2": unit((n_stages, lps, d), jnp.float32),
        "final_ln": unit((d,), jnp.float32),
    }
    if cfg.post_norms:
        params["ln1_post"] = unit((n_stages, lps, d), jnp.float32)
        params["ln2_post"] = unit((n_stages, lps, d), jnp.float32)
    if cfg.residual_scales:  # a one, b zero, c one
        start = jnp.array([1.0, 0.0, 1.0], jnp.float32)[:, None]
        for name in ("res1", "res2"):
            params[name] = jnp.broadcast_to(start, (n_stages, lps, 3, d))
    if not cfg.tie_embeddings:
        params["head"] = norm(ks[4], (d, cfg.n_pred_heads * cfg.vocab),
                              d ** -0.5)
    if cfg.pos_table and not cfg.rope:
        params["pos"] = norm(ks[1], (cfg.max_seq, d), 0.02)
    if cfg.n_mtp_modules:
        params.update(_init_mtp(cfg, jax.random.fold_in(rng, 2), norm))
    for group, entry in _mixer_groups(cfg.kinds).items():
        params.update(entry.init(
            cfg, rng, (n_stages, stage.count(group)), norm))
    Le, Ld = ffns.count("moe"), ffns.count("mlp")
    if Le:
        E, Eh, Fe = cfg.n_experts, cfg.experts_held, cfg.d_expert
        Fs = cfg.n_shared_experts * Fe
        if cfg.router_hidden:
            params.update(_init_router_mlp(
                cfg, jax.random.fold_in(rng, 4), (n_stages, Le)))
        else:
            params["router"] = (jax.random.normal(
                ks[5], (n_stages, Le, d, E)) * d ** -0.5)
        params.update({
            "wg": norm(ks[6], (n_stages, Le, Eh, d, Fe), d ** -0.5),
            "wu": norm(ks[9], (n_stages, Le, Eh, d, Fe), d ** -0.5),
            "wd": norm(ks[7], (n_stages, Le, Eh, Fe, d), Fe ** -0.5),
        })
        if Fs:
            params["shared_wgu"] = norm(
                k_shared_gu, (n_stages, Le, d, 2, Fs), d ** -0.5)
            params["shared_w2"] = norm(
                k_shared_2, (n_stages, Le, Fs, d), Fs ** -0.5)
        if cfg.expert_bias_rate:
            params["expert_bias"] = jnp.zeros((n_stages, Le, E),
                                              jnp.float32)
    # A model of dense layers alone takes the keys it always took.
    k_gu, k_2 = (k_dense_gu, k_dense_2) if Le else (ks[5], ks[6])
    if Ld:
        name, width = ("wgu", (2, F)) if cfg.gated_mlp else ("w1", (F,))
        params[name] = norm(k_gu, (n_stages, Ld, d) + width, d ** -0.5)
        params["w2"] = norm(k_2, (n_stages, Ld, F, d), F ** -0.5)
    return params


def _init_attention(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The attention mixers' leaves with leading shape ``lead``: matrices
    normal at fan-in^-1/2 under the keys they have always had, of the
    model's root (``init_params``), the QK-norms' weights one (float32)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head
    ks = jax.random.split(rng, 12)
    params = {"wo": norm(ks[3], lead + (H, Dh, d), (H * Dh) ** -0.5)}
    if Hkv == H:
        params["wqkv"] = norm(ks[2], lead + (d, 3, H, Dh), d ** -0.5)
    else:
        params["wq"] = norm(ks[2], lead + (d, H, Dh), d ** -0.5)
        params["wkv"] = norm(ks[8], lead + (d, 2, Hkv, Dh), d ** -0.5)
    if cfg.qk_norm:
        heads = ((), ()) if cfg.qk_norm == "head" else ((H,), (Hkv,))
        params.update({name: jnp.ones(lead + h + (Dh,), jnp.float32)
                       for name, h in zip(("gq", "gk"), heads)})
    if cfg.attn_gate:
        params["wgate"] = norm(jax.random.split(ks[11], 5)[0],
                               lead + (d, H, Dh), d ** -0.5)
    return params


def _init_latent(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The latent mixers' leaves with leading shape ``lead``: matrices
    normal at fan-in^-1/2, the two norms' weights one (float32)."""
    d, H = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    rope, nope = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
    Dqk, Dv = nope + rope, cfg.v_head_dim or cfg.d_head
    ks = jax.random.split(rng, 5)
    params = {
        "l_wkva": norm(ks[2], lead + (d, rkv + rope), d ** -0.5),
        "l_kvnorm": jnp.ones(lead + (rkv,), jnp.float32),
        "l_wkvb": norm(ks[3], lead + (rkv, H, nope + Dv), rkv ** -0.5),
        "l_wo": norm(ks[4], lead + (H, Dv, d), (H * Dv) ** -0.5),
    }
    if rq:
        params.update({
            "l_wqa": norm(ks[0], lead + (d, rq), d ** -0.5),
            "l_qnorm": jnp.ones(lead + (rq,), jnp.float32),
            "l_wqb": norm(ks[1], lead + (rq, H, Dqk), rq ** -0.5)})
    else:
        params["l_wq"] = norm(ks[0], lead + (d, H, Dqk), d ** -0.5)
    if cfg.qk_norm:
        params.update({name: jnp.ones(lead + (Dqk,), jnp.float32)
                       for name in ("l_gq", "l_gk")})
    if cfg.attn_gate:
        params["l_wgate"] = norm(jax.random.fold_in(rng, 7),
                                 lead + (d, H), d ** -0.5)
    return params


def _init_kda(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The KDA mixers' leaves with leading shape ``lead``: matrices normal
    at fan-in^-1/2, the convolutions uniform at taps^-1/2 (no bias), the
    output norm's weight one, and the decay's three parts so that a token's
    log decay starts log-uniform in [-1e-1, -1e-3] before the projection
    moves it: ``A`` zero and the bias the logit of that over the floor
    (float32)."""
    d, H, Dk, taps = cfg.d_model, cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv
    ks = jax.random.split(rng, 8)
    f32 = jnp.float32
    slow = jnp.exp(jax.random.uniform(ks[5], lead + (H, Dk), f32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    share = slow / -cfg.kda_gate_floor
    return {
        "k_wqkv": norm(ks[0], lead + (d, 3, H, Dk), d ** -0.5),
        "k_conv": jax.random.uniform(ks[1], lead + (taps, 3, H, Dk), f32,
                                     -1.0, 1.0) * taps ** -0.5,
        "k_wf": norm(ks[2], lead + (d, H, Dk), d ** -0.5),
        "k_fb": jnp.log(share) - jnp.log1p(-share),
        "k_A": jnp.zeros(lead + (H,), f32),
        "k_wbeta": norm(ks[3], lead + (d, H), d ** -0.5),
        "k_wg": norm(ks[4], lead + (d, H, Dk), d ** -0.5),
        "k_norm": jnp.ones(lead + (Dk,), f32),
        "k_wo": norm(ks[6], lead + (H, Dk, d), (H * Dk) ** -0.5),
    }


def _init_cca(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The CCA mixers' leaves with leading shape ``lead``: matrices normal
    at fan-in^-1/2, both convolutions' filters normal at (taps x fan-in)
    ^-1/2 (the depthwise one has fan-in one), the temperature one
    (float32)."""
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head
    K0, K1 = cfg.cca_time0, cfg.cca_time1
    ks = jax.random.split(rng, 8)
    return {
        "c_wq": norm(ks[0], lead + (d, H, Dh), d ** -0.5),
        "c_wk": norm(ks[1], lead + (d, Hkv, Dh), d ** -0.5),
        "c_wv": norm(ks[2], lead + (d, Hkv, Dh), d ** -0.5),
        "c_conv0_q": norm(ks[3], lead + (K0, H, Dh), K0 ** -0.5),
        "c_conv0_k": norm(ks[4], lead + (K0, Hkv, Dh), K0 ** -0.5),
        "c_conv1_q": norm(ks[5], lead + (K1, H, Dh, Dh),
                          (K1 * Dh) ** -0.5),
        "c_conv1_k": norm(ks[6], lead + (K1, Hkv, Dh, Dh),
                          (K1 * Dh) ** -0.5),
        "c_beta": jnp.ones(lead + (Hkv,), jnp.float32),
        "c_wo": norm(ks[7], lead + (H, Dh, d), (H * Dh) ** -0.5),
    }


def _init_eva(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The EVA mixers' leaves with leading shape ``lead``: matrices normal
    at fan-in^-1/2, the two pooling vectors of a head normal at D^-1/2
    (float32)."""
    d, H, Dh = cfg.d_model, cfg.n_heads, cfg.d_head
    ks = jax.random.split(rng, 4)
    return {
        "e_wqkv": norm(ks[0], lead + (d, 3, H, Dh), d ** -0.5),
        "e_mu": jax.random.normal(ks[1], lead + (H, Dh)) * Dh ** -0.5,
        "e_phi": jax.random.normal(ks[2], lead + (H, Dh)) * Dh ** -0.5,
        "e_wo": norm(ks[3], lead + (H, Dh, d), (H * Dh) ** -0.5),
    }


def _init_router_mlp(cfg: TransformerConfig, rng, lead) -> Dict:
    """The ZAYA routers' leaves with leading shape ``lead``, all float32:
    matrices normal at fan-in^-1/2, the carried state's weight ``gamma``
    and the norm's weight one."""
    d, R, E = cfg.d_model, cfg.router_hidden, cfg.n_experts
    ks = jax.random.split(rng, 4)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, lead + shape) * fan_in ** -0.5

    return {
        "r_down": normal(ks[0], (d, R), d),
        "r_gamma": jnp.ones(lead + (1,), jnp.float32),
        "r_norm": jnp.ones(lead + (R,), jnp.float32),
        "r_w1": normal(ks[1], (R, R), R),
        "r_w2": normal(ks[2], (R, R), R),
        "r_w3": normal(ks[3], (R, E), R),
    }


def _init_mtp(cfg: TransformerConfig, rng, norm) -> Dict:
    """The multi-token-prediction module's leaves, the modules (one) as
    their leading dimension: its layer's as ``init_params`` makes a
    one-layer model's, the three norms' weights one, ``mtp_eh`` normal at
    (2 d)^-1/2."""
    d, M = cfg.d_model, cfg.n_mtp_modules
    k_layer, k_eh = jax.random.split(rng)
    layer = init_params(cfg.mtp_layer, k_layer, n_stages=1)
    params = {_MTP + name: leaf[0] for name, leaf in layer.items()
              if name not in _MODEL_LEAVES}
    params.update({name: jnp.ones((M, d), jnp.float32)
                   for name in _MTP_LEAVES})
    params["mtp_eh"] = norm(k_eh, (M, 2 * d, d), (2 * d) ** -0.5)
    return params


def _init_mamba(cfg: TransformerConfig, rng, lead, norm) -> Dict:
    """The Mamba-2 mixers' leaves with leading shape ``lead``: matrices
    normal at fan-in^-1/2, the convolution uniform at k^-1/2 as
    ``nn.Conv1d``'s, and Mamba-2's own defaults for the rest: ``dt_bias``
    the inverse softplus of a step log-uniform in [1e-3, 1e-1], ``A``
    uniform in [1, 16], ``D`` one, the norm's scale one (float32)."""
    d, H, Pm, N, K = (cfg.d_model, cfg.mamba_heads, cfg.mamba_d_head,
                      cfg.mamba_d_state, cfg.mamba_d_conv)
    ks = jax.random.split(rng, 10)
    f32 = jnp.float32

    def conv(key, shape):
        return jax.random.uniform(key, lead + shape, f32, -1.0, 1.0) \
            * K ** -0.5

    step = jnp.exp(jax.random.uniform(ks[7], lead + (H,), f32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "m_wzx": norm(ks[0], lead + (d, 2, H, Pm), d ** -0.5),
        "m_wbc": norm(ks[1], lead + (d, 2, N), d ** -0.5),
        "m_wdt": norm(ks[2], lead + (d, H), d ** -0.5),
        "m_conv_x": conv(ks[3], (K, H, Pm)),
        "m_conv_xb": conv(ks[4], (H, Pm)),
        "m_conv_bc": conv(ks[5], (K, 2, N)),
        "m_conv_bcb": conv(ks[6], (2, N)),
        "m_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "m_A_log": jnp.log(jax.random.uniform(ks[8], lead + (H,), f32,
                                              1.0, 16.0)),
        "m_D": jnp.ones(lead + (H,), f32),
        "m_g": jnp.ones(lead + (H, Pm), f32),
        "m_wo": norm(ks[9], lead + (H, Pm, d), (H * Pm) ** -0.5),
    }


def _pipeline_stages(mesh) -> int:
    return dict(mesh.shape).get("pp", 1)


def _validate_mesh_divisibility(cfg: TransformerConfig, mesh) -> None:
    """Head counts must divide the tp axis: wq/wqkv shard the query-head
    dim and wkv the KV-head dim over 'tp', and an indivisible split only
    surfaces later as an opaque XLA sharding error at compile time.
    Checked here — where the mesh is known — rather than in
    ``__post_init__``, which never sees it. Likewise a pipeline whose
    stages would not hold whole periods of the layer pattern, and what
    the model's layers refuse across ``sp`` or ``pp`` (``_refuse``)."""
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if cfg.n_heads % tp != 0:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must be divisible by the mesh's tp "
            f"axis ({tp}) — wq/wqkv shard the head dim over tp")
    if cfg.kv_heads % tp != 0:
        raise ValueError(
            f"kv_heads ({cfg.kv_heads}) must be divisible by the mesh's "
            f"tp axis ({tp}) — wkv shards the KV-head dim over tp; use "
            f"n_kv_heads that is a multiple of tp (or tp <= n_kv_heads)")
    cfg.stage_pattern(_pipeline_stages(mesh))
    for axis in ("sp", "pp"):
        if shape.get(axis, 1) > 1:
            _refuse(cfg, axis)
    for kind in dict.fromkeys(cfg.mixer_kinds):
        for name in MIXERS[kind].tp_divides:
            if getattr(cfg, name) % tp != 0:
                raise ValueError(
                    f"{name} ({getattr(cfg, name)}) must be divisible by "
                    f"the mesh's tp axis ({tp}) — the mixer shards its "
                    f"heads over tp")


def _model_counts(cfg: TransformerConfig) -> Dict[str, int]:
    """What the set-up spans and the ``model.*`` counters say of a model
    with either: its layers of each kind whose entry names a count (a
    multi-token-prediction module's among them), those modules, and the
    head's predictions a position where they are more than one."""
    layers = cfg.mixer_kinds
    counts = {MIXERS[kind].counts: layers.count(kind)
              for kind in dict.fromkeys(layers) if MIXERS[kind].counts}
    counts["mtp_modules"] = cfg.n_mtp_modules
    counts["pred_heads"] = cfg.n_pred_heads if cfg.n_pred_heads > 1 else 0
    counts["router_groups_kept"] = (cfg.moe_topk_group
                                    if cfg.moe_n_group > 1 else 0)
    return {k: n for k, n in counts.items() if n}


def shard_params(params: Dict, cfg: TransformerConfig, mesh) -> Dict:
    _validate_mesh_divisibility(cfg, mesh)
    specs = _param_specs(cfg)
    with _metrics.span("state.shard", **_metrics.tree_counts(params),
                       **_model_counts(cfg)):
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()
        }


def _rope(x, positions, theta, last=None):
    """Rotary position embeddings (rotate-half convention).

    x: [b, t, H, Dh] (Dh even); positions: [t] GLOBAL token positions —
    sequence-parallel shards pass their offset range, which is what
    makes RoPE compose with the sp axis. ``last`` (even) rotates the
    last ``last`` channels of a head as one whole rotated head and leaves
    the channels before them as they are."""
    if last is None or last == x.shape[-1]:
        return _rotated(x, positions, theta)
    keep = x.shape[-1] - last
    return jnp.concatenate([
        x[..., :keep], _rotated(x[..., keep:], positions, theta)], -1)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _rotated(x, positions, theta):
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], -1).astype(x.dtype)


def _layernorm(x, scale):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + 1e-5) * scale).astype(x.dtype)


# The new norms keep their operand in its own type for the backward pass
# and form the float32 values anew (jax.checkpoint): at [b, 4096, 2048]
# the float32 copies of a layer would otherwise be kept six times over.
@functools.partial(jax.checkpoint, static_argnums=(2,))
def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4))
def _rmsnorm_as(x, scale, eps, offset, dtype):
    """``_rmsnorm`` scaled by ``offset + scale``, written in ``dtype``
    whatever the operand's type (a float32 stream's norms)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * (offset + scale)).astype(dtype)


def _block_norm(cfg: TransformerConfig):
    """norm(x, scale) of the residual stream, by ``cfg.norm``; the
    model's type from a float32 stream."""
    if cfg.norm_unit_offset or cfg.float32_stream:
        return lambda x, scale: _rmsnorm_as(
            x, scale, cfg.norm_eps, float(cfg.norm_unit_offset), cfg.dtype)
    if cfg.norm == "rmsnorm":
        return lambda x, scale: _rmsnorm(x, scale, cfg.norm_eps)
    return _layernorm


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _qk_norm(x, scale, eps):
    """RMSNorm of projected queries or keys x [b, t, h, k] over the whole
    projected vector: every head of every tp member."""
    xf = x.astype(jnp.float32)
    width = x.shape[2] * x.shape[3] * _axis_size("tp")
    ss = lax.psum(jnp.sum(jnp.square(xf), (2, 3), keepdims=True), "tp")
    return (xf * jax.lax.rsqrt(ss / width + eps) * scale).astype(x.dtype)


@jax.checkpoint
def _sigmoid_gated(attn, gate):
    """``attn * sigmoid(gate)`` in float32; the backward pass keeps the
    operands in their own type."""
    return (attn.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)


def _zero_router_stats(cfg: TransformerConfig, lead):
    """What the MoE layers add up as the activations pass through them,
    with leading shape ``lead``: the two loss terms as means over all
    layers, tokens per expert and the windows of the sorted assignments
    taken (``parallel.moe``) by layer."""
    return {"lb": jnp.zeros(lead, jnp.float32),
            "z": jnp.zeros(lead, jnp.float32),
            "load": jnp.zeros(lead + (cfg.n_layers, cfg.n_experts),
                              jnp.int32),
            "windows": jnp.zeros(lead + (cfg.n_layers,), jnp.int32)}


def _causal_depthwise_conv(x, w, bias):
    """``y_t = sum_k w[k] * x[t - (K - 1) + k] + bias`` along axis 1 of x
    [b, T, ...] with w [K, ...]: each channel its own filter, zeros
    before the sequence's first token."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    y = sum(xp[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
    return (y + bias).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _gated_rmsnorm(y, z, scale, eps):
    """``rmsnorm(y * silu(z)) * scale`` of y, z [b, t, h, p] over all
    heads and channels of every tp member (one group), in float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    width = y.shape[2] * y.shape[3] * _axis_size("tp")
    ss = lax.psum(jnp.sum(jnp.square(v), (2, 3), keepdims=True), "tp")
    return (v * jax.lax.rsqrt(ss / width + eps) * scale).astype(y.dtype)


# What a rematerialized layer (``TransformerConfig.remat``) keeps of its
# forward pass beside its input unless ``remat_keeps`` names others of
# ``REMAT_NAMES``: the Mamba in-projection's z and x, and
# the scan's output, so that the layer's second forward leaves out that
# matmul and the scan's forward kernel (``ssd_fwd`` is dead code there:
# the scan's ``custom_vjp`` keeps its inputs only, and its backward kernel
# forms the decay and score tiles anew in VMEM; the sums and the chunk
# states, XLA's, are computed again). Three [t, H P] arrays a layer in the
# model's type; in granite-h-t8192 8.7 % more tokens a second for 3.3 GiB
# (PERF.md section 6, PR 30).
_REMAT_KEEPS = ("mamba_zx", "ssd_out")


def _mamba_mixer(cfg: TransformerConfig, h, lp):
    """The Mamba-2 mixer of the module's docstring on normed h [b, t, d];
    heads are this tp member's, the result its partial sum."""
    with jax.named_scope("mamba_in_proj"):
        zx = checkpoint_name(
            jnp.einsum("btd,dchp->btchp", h, lp["m_wzx"]),  # h=H/tp
            "mamba_zx")
        z, xs = zx[:, :, 0], zx[:, :, 1]
        bc = jnp.einsum("btd,dcn->btcn", h, lp["m_wbc"])
        dt = jnp.einsum("btd,dh->bth", h, lp["m_wdt"])
    with jax.named_scope("mamba_conv"):
        xs = jax.nn.silu(_causal_depthwise_conv(
            xs, lp["m_conv_x"], lp["m_conv_xb"]))
        bc = jax.nn.silu(_causal_depthwise_conv(
            bc, lp["m_conv_bc"], lp["m_conv_bcb"]))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["m_dt_bias"])
    with jax.named_scope("ssd"):
        y = checkpoint_name(
            ssd_chunked(xs, dt, -jnp.exp(lp["m_A_log"]), bc[:, :, 0],
                        bc[:, :, 1], lp["m_D"], cfg.mamba_chunk), "ssd_out")
    with jax.named_scope("mamba_gate_norm"):
        y = _gated_rmsnorm(y, z, lp["m_g"], cfg.norm_eps)
    with jax.named_scope("mamba_out_proj"):
        return jnp.einsum("bthp,hpd->btd", y, lp["m_wo"])


def _latent_mixer(cfg: TransformerConfig, h, lp):
    """The latent attention mixer of the module's docstring on normed h
    [b, t, d]; heads are this tp member's, the result its partial sum."""
    rkv, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, theta = cfg.qk_nope_head_dim, cfg.rope_theta
    b, t, _ = h.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    with jax.named_scope("mla_q"):
        if "l_wq" in lp:  # the query's one full-rank matrix
            q = checkpoint_name(
                jnp.einsum("btd,dhk->bthk", h, lp["l_wq"]), "attn_q")
        else:
            c_q = checkpoint_name(
                jnp.einsum("btd,dr->btr", h, lp["l_wqa"]), "mla_cq")
            q = checkpoint_name(jnp.einsum(
                "btr,rhk->bthk", _rmsnorm(c_q, lp["l_qnorm"], cfg.norm_eps),
                lp["l_wqb"]), "attn_q")  # h=H/tp
        if cfg.qk_norm:  # over a head's channels, before the rotation
            q = _rmsnorm(q, lp["l_gq"], cfg.norm_eps)
        q = _rope(q, pos, theta, rope)
    with jax.named_scope("mla_kv"):
        ckv = checkpoint_name(
            jnp.einsum("btd,dr->btr", h, lp["l_wkva"]), "mla_ckv")
        kv = checkpoint_name(jnp.einsum(
            "btr,rhk->bthk",
            _rmsnorm(ckv[..., :rkv], lp["l_kvnorm"], cfg.norm_eps),
            lp["l_wkvb"]), "attn_kv")
        # One rotated head, read by every query head.
        k_rope = ckv[:, :, None, rkv:]
        if cfg.qk_norm:
            # A head's key is normed whole, its own channels beside the
            # shared ones, so the shared ones are normed a head at a time.
            k = _rmsnorm(jnp.concatenate([
                kv[..., :nope],
                jnp.broadcast_to(k_rope, (b, t, kv.shape[2], rope))], -1),
                lp["l_gk"], cfg.norm_eps)
            k = _rope(k, pos, theta, rope)
        else:
            k_rope = _rope(k_rope, pos, theta)
            k = jnp.concatenate([
                kv[..., :nope],
                jnp.broadcast_to(k_rope, (b, t, kv.shape[2], rope))], -1)
    v = kv[..., nope:]
    narrower = q.shape[-1] - v.shape[-1]
    if narrower:
        # The kernels take one width: the values ride zeros up to the
        # keys', and the zeros' columns of the result are dropped.
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, narrower)])
    attn = context_parallel_attention(
        q, k, v, axis_name="sp", causal=True, strategy=cfg.sp_strategy)
    if narrower:
        attn = attn[..., :-narrower]
    if cfg.attn_gate:  # one gate a head
        with jax.named_scope("attn_gate"):
            attn = _sigmoid_gated(attn, checkpoint_name(jnp.einsum(
                "btd,dh->bth", h, lp["l_wgate"]), "attn_gate")[..., None])
    return checkpoint_name(
        jnp.einsum("bthk,hkd->btd", attn, lp["l_wo"]), "attn_proj")


@functools.partial(jax.checkpoint, static_argnums=(1,))
def _l2_normed(x, scale):
    """``scale * x / |x|`` over the last axis in float32 (1e-6 under the
    root), in x's type."""
    xf = x.astype(jnp.float32)
    ss = jnp.sum(jnp.square(xf), -1, keepdims=True)
    return (xf * (scale * jax.lax.rsqrt(ss + 1e-6))).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _kda_decay(a, bias, A, floor):
    """A token's log decay by channel, float32 in (floor, 0): ``floor *
    sigmoid(exp(A_h) (a + bias))`` of the projection a [b, t, h, k]."""
    return floor * jax.nn.sigmoid(
        jnp.exp(A)[:, None] * (a.astype(jnp.float32) + bias))


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _head_norm_gated(o, gate, scale, eps):
    """``rmsnorm(o) * scale * sigmoid(gate)`` over each head's channels of
    o, gate [b, t, h, k] in float32, in o's type."""
    of = o.astype(jnp.float32)
    ms = jnp.mean(jnp.square(of), -1, keepdims=True)
    return (of * jax.lax.rsqrt(ms + eps) * scale
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


def _kda_mixer(cfg: TransformerConfig, h, lp):
    """The KDA mixer of the module's docstring on normed h [b, t, d];
    heads are this tp member's, the result its partial sum."""
    with jax.named_scope("kda_proj"):
        qkv = checkpoint_name(
            jnp.einsum("btd,dchk->btchk", h, lp["k_wqkv"]),  # h=H/tp
            "kda_qkv")
        a = checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, lp["k_wf"]), "kda_gates")
        gate = checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, lp["k_wg"]), "kda_gates")
        beta = jnp.einsum("btd,dh->bth", h, lp["k_wbeta"])
    with jax.named_scope("kda_conv"):
        qkv = jax.nn.silu(_causal_depthwise_conv(qkv, lp["k_conv"], 0.0))
    with jax.named_scope("kda_gate"):
        q = _l2_normed(qkv[:, :, 0], cfg.kda_head_dim ** -0.5)
        k = _l2_normed(qkv[:, :, 1], 1.0)
        g = _kda_decay(a, lp["k_fb"], lp["k_A"], cfg.kda_gate_floor)
        beta = jax.nn.sigmoid(beta.astype(jnp.float32))
    with jax.named_scope("kda_scan"):
        o = checkpoint_name(
            kda_chunked(q, k, qkv[:, :, 2], g, beta, cfg.kda_chunk),
            "kda_out")
    with jax.named_scope("kda_gate"):
        o = _head_norm_gated(o, gate, lp["k_norm"], cfg.norm_eps)
    with jax.named_scope("kda_out"):
        return checkpoint_name(
            jnp.einsum("bthk,hkd->btd", o, lp["k_wo"]), "attn_proj")


def _causal_conv_by_head(x, w):
    """``y_t[i] = sum_j x_{t - (K - 1) + j}[i] W[j, i]`` along axis 1 of x
    [b, T, h, k] with w [K, h, k, c]: each head its own [k, c] matrix a
    tap, zeros before the sequence's first token. One matmul a head over
    the taps' channels side by side (contraction K k), operands as they
    are."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0), (0, 0)])
    taps = jnp.concatenate([xp[:, j:j + T] for j in range(K)], -1)
    return jnp.einsum("bthk,hkc->bthc", taps,
                      w.transpose(1, 0, 2, 3).reshape(
                          w.shape[1], K * w.shape[2], w.shape[3]))


def _cca_mixer(cfg: TransformerConfig, h, lp):
    """The CCA mixer of the module's docstring on normed h [b, t, d];
    heads are this tp member's (whole groups), the result its partial
    sum."""
    b, t, _ = h.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    rotated = int(cfg.partial_rotary_factor * cfg.d_head)
    with jax.named_scope("cca_q"):
        q0 = checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, lp["c_wq"]), "cca_q")  # h=H/tp
    with jax.named_scope("cca_kv"):
        k0 = checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, lp["c_wk"]), "cca_kv")
        v = jnp.einsum("btd,dhk->bthk", h, lp["c_wv"])
    hkv = v.shape[2]
    g = q0.shape[2] // hkv
    with jax.named_scope("cca_conv"):
        # The upper half of the value heads (of all tp members') read the
        # token before: h_{t-1} W = (h W)_{t-1}.
        head = lax.axis_index("tp") * hkv + jnp.arange(hkv)
        before = jnp.pad(v, [(0, 0), (1, 0), (0, 0), (0, 0)])[:, :t]
        v = checkpoint_name(
            jnp.where((head >= cfg.kv_heads // 2)[:, None], before, v),
            "cca_kv")
        q1, k1 = (checkpoint_name(_causal_conv_by_head(
            _causal_depthwise_conv(x, lp["c_conv0_" + n], 0.0),
            lp["c_conv1_" + n]), "cca_conv") for x, n in ((q0, "q"),
                                                          (k0, "k")))
    with jax.named_scope("cca_qk_mean"):
        qf = q0.astype(jnp.float32).reshape(b, t, hkv, g, -1)
        kf = k0.astype(jnp.float32)
        q = q1.astype(jnp.float32) + 0.5 * (
            qf + kf[:, :, :, None]).reshape(q0.shape)
        k = k1.astype(jnp.float32) + 0.5 * (jnp.mean(qf, axis=3) + kf)
    with jax.named_scope("cca_norm"):
        # sqrt(Dh) x / |x| is the RMSNorm over a head's channels; the
        # keys' weight is their head's temperature.
        q = _rmsnorm(q, jnp.ones((), jnp.float32), cfg.norm_eps)
        k = _rmsnorm(k, lp["c_beta"][:, None], cfg.norm_eps)
        q, k = (jnp.concatenate([
            _rotated(x[..., :rotated], pos, cfg.rope_theta),
            x[..., rotated:]], -1).astype(h.dtype) for x in (q, k))
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_kv")
    attn = context_parallel_attention(
        q, k, v, axis_name="sp", causal=True, strategy=cfg.sp_strategy)
    with jax.named_scope("cca_out"):
        return checkpoint_name(
            jnp.einsum("bthk,hkd->btd", attn, lp["c_wo"]), "attn_proj")


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _eva_summaries(k, v, mu, phi, chunk):
    """A chunk's summary key and value of rotated k and v [b, t, h, D]
    under a head's pooling vectors mu, phi [h, D]: ``(sum_m softmax_m(mu .
    k_m) k_m, sum_m softmax_m(phi . k_m) v_m)`` over each chunk's tokens,
    [b, t / chunk, h, D] in k's type, computed in float32 (formed anew in
    the backward pass, as the norms' values are)."""
    b, t, h, D = k.shape
    kf, vf = (x.astype(jnp.float32).reshape(b, t // chunk, chunk, h, D)
              for x in (k, v))

    def pooled(w, x):
        a = jax.nn.softmax(jnp.sum(kf * w, -1), axis=2)  # [b, n, chunk, h]
        return jnp.sum(a[..., None] * x, axis=2).astype(k.dtype)

    return pooled(mu, kf), pooled(phi, vf)


def _eva_mixer(cfg: TransformerConfig, h, lp):
    """The EVA mixer of the module's docstring on normed h [b, t, d];
    heads are this tp member's, the result its partial sum."""
    from ..ops.eva_attention import eva_attention

    t = h.shape[1]
    pos = jnp.arange(t, dtype=jnp.int32)
    with jax.named_scope("eva_qkv"):
        qkv = jnp.einsum("btd,dchk->btchk", h, lp["e_wqkv"])  # h=H/tp
        q = checkpoint_name(_rope(qkv[:, :, 0], pos, cfg.rope_theta),
                            "attn_q")
        k = checkpoint_name(_rope(qkv[:, :, 1], pos, cfg.rope_theta),
                            "attn_kv")
        v = checkpoint_name(qkv[:, :, 2], "attn_kv")
    with jax.named_scope("eva_chunks"):
        k_sum, v_sum = _eva_summaries(k, v, lp["e_mu"], lp["e_phi"],
                                      cfg.eva_chunk)
    # A sequence inside one window is that window.
    attn = eva_attention(q, k, v, k_sum, v_sum, min(cfg.eva_window, t),
                         cfg.eva_chunk)
    with jax.named_scope("eva_out"):
        return checkpoint_name(
            jnp.einsum("bthk,hkd->btd", attn, lp["e_wo"]), "attn_proj")


def _attention_mixer(cfg: TransformerConfig, h, lp, seg, gathered_seg,
                     window, rope):
    """Softmax attention on normed h [b, t, d] over ``window`` tokens (None:
    all before), queries and keys rotated with ``rope``; heads are this tp
    member's, the sequence this sp member's, the result its partial sum."""
    if "wqkv" in lp:
        qkv = jnp.einsum("btd,dchk->btchk", h, lp["wqkv"])  # h=H/tp
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:  # GQA: separate q and (fewer-headed) kv projections
        q = checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, lp["wq"]), "attn_q")
        kv = checkpoint_name(
            jnp.einsum("btd,dchk->btchk", h, lp["wkv"]),  # h=Hkv/tp
            "attn_kv")
        k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.qk_norm == "head":  # [b, t, h, k] over k, one scale [k]
        q = _rmsnorm(q, lp["gq"], cfg.norm_eps)
        k = _rmsnorm(k, lp["gk"], cfg.norm_eps)
    elif cfg.qk_norm:
        q = _qk_norm(q, lp["gq"], cfg.norm_eps)
        k = _qk_norm(k, lp["gk"], cfg.norm_eps)
    if rope:
        t_local = h.shape[1]
        pos = (lax.axis_index("sp") * t_local
               + jnp.arange(t_local, dtype=jnp.int32))
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        # The kernels keep their d_head^-1/2; the rest goes on q.
        q = _times(q, cfg.attention_multiplier * cfg.d_head ** 0.5)
    # GQA K/V stay at their reduced head width here — the
    # context-parallel strategies carry them across the sp fabric
    # at that width and expand only at the kernel boundary.
    attn = context_parallel_attention(
        q, k, v, axis_name="sp", causal=True,
        strategy=cfg.sp_strategy, segment_ids=seg,
        gathered_segment_ids=gathered_seg, window=window)
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            attn = _sigmoid_gated(attn, checkpoint_name(jnp.einsum(
                "btd,dhk->bthk", h, lp["wgate"]), "attn_gate"))
    return checkpoint_name(
        jnp.einsum("bthk,hkd->btd", attn, lp["wo"]), "attn_proj")


def _check_mamba(cfg: TransformerConfig):
    if not (cfg.mamba_heads and cfg.mamba_d_head and cfg.mamba_d_state):
        raise ValueError("a mamba layer needs mamba_heads, "
                         "mamba_d_head and mamba_d_state")


def _check_sliding(cfg: TransformerConfig):
    if not cfg.sliding_window or cfg.d_head % 2 != 0:
        raise ValueError("a sliding_attention layer needs a "
                         "sliding_window and an even d_head")


def _check_latent(cfg: TransformerConfig):
    rope, nope = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
    if not (cfg.q_lora_rank >= 0 and cfg.kv_lora_rank > 0 and nope >= 0
            and rope > 0 and rope % 2 == 0):
        raise ValueError(
            "a latent_attention layer needs kv_lora_rank, qk_nope_head_dim "
            "and an even qk_rope_head_dim (q_lora_rank 0: a full-rank "
            "query)")
    if not cfg.v_head_dim and nope + rope != cfg.d_head:
        raise ValueError(
            f"qk_nope_head_dim + qk_rope_head_dim ({nope} + {rope}) must "
            f"be d_head ({cfg.d_head}), the value's width too: a value "
            f"width that differs from the key's is not built unless "
            f"v_head_dim states it")
    if not 0 <= cfg.v_head_dim <= nope + rope:
        raise ValueError(
            f"v_head_dim ({cfg.v_head_dim}) wider than a key ({nope} + "
            f"{rope}) is not built: the values ride zeros up to the keys' "
            f"width into the flash kernels")
    if (cfg.n_kv_heads is not None or cfg.qk_norm not in (False, "head")
            or cfg.attn_gate not in (False, "head")
            or cfg.attention_multiplier is not None):
        raise ValueError(
            "a latent_attention layer has every head its own key and "
            "value, QK-norm over a head's channels (qk_norm 'head') or "
            "none, one gate a head (attn_gate 'head') or none, and no "
            "attention_multiplier: n_kv_heads, qk_norm, attn_gate and "
            "attention_multiplier are not built through it otherwise")


def _check_kda(cfg: TransformerConfig):
    if cfg.kda_head_dim < 1 or cfg.kda_conv < 1 or cfg.kda_chunk < 1:
        raise ValueError("a kda layer needs kda_head_dim, kda_conv and "
                         "kda_chunk >= 1")
    if not -5.0 <= cfg.kda_gate_floor < 0:
        raise ValueError(
            f"kda_gate_floor ({cfg.kda_gate_floor}) must lie in [-5, 0): "
            f"the scan forms a block's decays around its first row, and "
            f"16 rows of -5 are what float32 holds (ops/kda.py)")


def _check_cca(cfg: TransformerConfig):
    rotated = cfg.partial_rotary_factor * cfg.d_head
    if (cfg.cca_time0 < 1 or cfg.cca_time1 < 1 or rotated % 2
            or not 0 < rotated <= cfg.d_head):
        raise ValueError(
            "a cca layer needs convolution widths cca_time0, cca_time1 "
            ">= 1 and an even count of rotated channels "
            "(partial_rotary_factor x d_head)")
    if cfg.kv_heads % 2:
        raise ValueError(
            f"a cca layer shifts the upper half of its value heads by "
            f"one token: kv_heads ({cfg.kv_heads}) must be even")
    if (cfg.qk_norm or cfg.attn_gate
            or cfg.attention_multiplier is not None):
        raise ValueError(
            "a cca layer norms its queries and keys itself and has "
            "neither gate nor attention_multiplier: qk_norm, attn_gate "
            "and attention_multiplier are not built through it")


def _check_eva(cfg: TransformerConfig):
    W, C = cfg.eva_window, cfg.eva_chunk
    if C < 1 or W < C or W % C or cfg.d_head % 2:
        raise ValueError(
            "an eva layer needs eva_window, whole chunks of eva_chunk >= 1, "
            "and an even d_head (its queries and keys are rotated whole)")
    if (cfg.n_kv_heads is not None or cfg.qk_norm or cfg.attn_gate
            or cfg.attention_multiplier is not None):
        raise ValueError(
            "an eva layer has every head its own key and value and neither "
            "QK-norm, gate nor attention_multiplier: n_kv_heads, qk_norm, "
            "attn_gate and attention_multiplier are not built through it")


def _latent_specs(cfg: TransformerConfig) -> Dict[str, P]:
    specs = {"l_wkva": P("pp"), "l_kvnorm": P("pp"),
             "l_wkvb": P("pp", None, None, "tp"),
             "l_wo": P("pp", None, "tp")}
    if cfg.q_lora_rank:
        specs.update(l_wqa=P("pp"), l_qnorm=P("pp"),
                     l_wqb=P("pp", None, None, "tp"))
    else:
        specs["l_wq"] = P("pp", None, None, "tp")
    if cfg.qk_norm:  # one [qk] vector for all heads
        specs.update(l_gq=P("pp"), l_gk=P("pp"))
    if cfg.attn_gate:
        specs["l_wgate"] = P("pp", None, None, "tp")
    return specs


def _attention_specs(cfg: TransformerConfig) -> Dict[str, P]:
    specs = {"wo": P("pp", None, "tp")}
    if cfg.kv_heads == cfg.n_heads:
        specs["wqkv"] = P("pp", None, None, None, "tp")
    else:
        specs["wq"] = P("pp", None, None, "tp")
        specs["wkv"] = P("pp", None, None, None, "tp")
    if cfg.qk_norm:  # "head": one [d_head] vector for all heads
        scale = P("pp") if cfg.qk_norm == "head" else P("pp", None, "tp")
        specs.update(gq=scale, gk=scale)
    if cfg.attn_gate:
        specs["wgate"] = P("pp", None, None, "tp")
    return specs


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One kind of layer, its entry in ``MIXERS``: all the module does by
    a layer's kind it reads here, and nothing else names a kind."""
    # The group of stacks its layers read; a group's kinds share its
    # leaves, and so ``specs`` and ``init``.
    group: str
    # specs(cfg) -> the PartitionSpec of each of the group's stacks [S, L,
    # ...]. Which leaves are the group's is read here: a prefix of its own.
    specs: Callable[[TransformerConfig], Dict[str, P]]
    # init(cfg, rng, lead, norm) -> those leaves with leading shape
    # ``lead``, from the model's root key (a new kind folds in a salt of
    # its own) and ``init_params``'s ``norm(key, shape, scale)``.
    init: Callable[..., Dict]
    # mixer(cfg, h, lp, seg, gathered_seg) -> this tp member's partial sum
    # [b, t, d] on normed h; ``seg`` the ids of packed documents, or None.
    mixer: Callable[..., Any]
    # check(cfg) raises what the kind needs of a configuration.
    check: Callable[[TransformerConfig], None] = lambda cfg: None
    # refuses(cfg) -> the ValueError's sentence for each of "packed"
    # documents, "sp" > 1 and "pp" > 1 that is not built through it.
    refuses: Callable[[TransformerConfig], Dict[str, str]] = lambda cfg: {}
    # Fields of the configuration (head counts) that tp must divide.
    tp_divides: Tuple[str, ...] = ()
    # The name under which ``_model_counts`` counts the kind's layers.
    counts: Optional[str] = None


def _attending(window, rope, **more) -> Mixer:
    """The entry of a kind that attends with the shared attention leaves
    over ``window(cfg)`` tokens, rotated where ``rope(cfg)``."""
    return Mixer(
        group="attention", specs=_attention_specs, init=_init_attention,
        mixer=lambda cfg, h, lp, seg, gathered_seg: _attention_mixer(
            cfg, h, lp, seg, gathered_seg, window(cfg), rope(cfg)), **more)


def _across(rest: str) -> Dict[str, str]:
    """The refusals of sequence shards and of pipeline stages, ``rest``
    what they are refused through and why."""
    return {"sp": f"sequence shards (sp > 1) {rest}",
            "pp": f"pipeline stages (pp > 1) {rest}"}


_NO_SEGMENT_MASK = {"packed": (
    "packed documents through sliding_attention / full_attention layers "
    "are not built: no test holds their masks together with a segment's")}
# A multi-token-prediction module refuses what a latent layer does, the
# router's carried state what a CCA layer does across members.
_LATENT_REFUSES = {
    "packed": ("packed documents through a latent_attention layer or a "
               "multi-token-prediction module are not built: no test holds "
               "a segment's mask or its last label through them"),
    **_across("through a latent_attention layer or a "
              "multi-token-prediction module are not built: no test holds "
              "the shared rotated key or the module's shifted labels "
              "across them")}
_CCA_ACROSS = _across(
    "through a cca layer or the router's carried state are not built: the "
    "convolutions' and the shifted values' last rows are not handed to the "
    "next sp member, and no test holds the router's state on the "
    "pipeline's ring")

# A mixer function is looked up when a layer is traced, as is what it
# calls: benchmark/limit_check_*.py replace some in this module.
MIXERS: Dict[str, Mixer] = {
    # The model's one ``attention_window`` and ``rope`` switch.
    "attention": _attending(lambda cfg: cfg.attention_window,
                            lambda cfg: cfg.rope),
    "mamba": Mixer(
        group="mamba",
        # Heads over tp; B, C and their convolution channels whole.
        specs=lambda cfg: {
            "m_wzx": P("pp", None, None, None, "tp"), "m_wbc": P("pp"),
            "m_wdt": P("pp", None, None, "tp"),
            "m_conv_x": P("pp", None, None, "tp"),
            "m_conv_bc": P("pp"), "m_conv_bcb": P("pp"),
            **{k: P("pp", None, "tp") for k in (
                "m_conv_xb", "m_dt_bias", "m_A_log", "m_D", "m_g", "m_wo")}},
        init=lambda cfg, rng, lead, norm: _init_mamba(
            cfg, jax.random.split(rng, 12)[10], lead, norm),
        mixer=lambda cfg, h, lp, seg, gathered_seg: _mamba_mixer(cfg, h, lp),
        check=_check_mamba, tp_divides=("mamba_heads",),
        refuses=lambda cfg: {
            "packed": ("packed sequences cannot pass a mamba layer: the "
                       "scan's state and the convolution are not reset at "
                       "a segment boundary"),
            "sp": ("a mamba layer cannot run over sp > 1: the scan's state "
                   "at a shard's last token and the convolution's last "
                   f"{cfg.mamba_d_conv - 1} rows are not handed to the "
                   "next sp member")}),
    # Over ``sliding_window`` tokens, always rotated.
    "sliding_attention": _attending(
        lambda cfg: cfg.sliding_window, lambda cfg: True,
        check=_check_sliding, refuses=lambda cfg: _NO_SEGMENT_MASK),
    # Over everything before, no positions.
    "full_attention": _attending(
        lambda cfg: None, lambda cfg: False,
        refuses=lambda cfg: _NO_SEGMENT_MASK),
    "latent_attention": Mixer(
        group="latent",
        # Heads over tp; the down-projections and their norms whole.
        specs=_latent_specs,
        init=lambda cfg, rng, lead, norm: _init_latent(
            cfg, jax.random.fold_in(rng, 1), lead, norm),
        mixer=lambda cfg, h, lp, seg, gathered_seg: _latent_mixer(
            cfg, h, lp),
        check=_check_latent, refuses=lambda cfg: _LATENT_REFUSES,
        counts="latent_layers"),
    "cca": Mixer(
        group="cca",
        # Heads over tp, the filters and the temperature with them.
        specs=lambda cfg: {
            **{k: P("pp", None, None, "tp") for k in (
                "c_wq", "c_wk", "c_wv", "c_conv0_q", "c_conv0_k",
                "c_conv1_q", "c_conv1_k")},
            "c_beta": P("pp", None, "tp"), "c_wo": P("pp", None, "tp")},
        init=lambda cfg, rng, lead, norm: _init_cca(
            cfg, jax.random.fold_in(rng, 3), lead, norm),
        mixer=lambda cfg, h, lp, seg, gathered_seg: _cca_mixer(cfg, h, lp),
        check=_check_cca,
        refuses=lambda cfg: {
            "packed": ("packed documents through a cca layer are not "
                       "built: the convolutions and the shifted values are "
                       "not reset at a segment boundary"), **_CCA_ACROSS}),
    "eva": Mixer(
        group="eva",
        # Heads over tp, the pooling vectors with them.
        specs=lambda cfg: {
            "e_wqkv": P("pp", None, None, None, "tp"),
            "e_mu": P("pp", None, "tp"), "e_phi": P("pp", None, "tp"),
            "e_wo": P("pp", None, "tp")},
        init=lambda cfg, rng, lead, norm: _init_eva(
            cfg, jax.random.fold_in(rng, 5), lead, norm),
        mixer=lambda cfg, h, lp, seg, gathered_seg: _eva_mixer(cfg, h, lp),
        check=_check_eva,
        refuses=lambda cfg: {
            "packed": ("packed documents through an eva layer are not "
                       "built: the windows and the chunks are counted from "
                       "the sequence's first token, not a segment's"),
            "sp": ("sequence shards (sp > 1) through an eva layer are not "
                   "built: a shard's queries would need the chunk summaries "
                   "of every earlier shard, and no exchange hands them on"),
            "pp": ("pipeline stages (pp > 1) through an eva layer are not "
                   "built: no test holds the pipeline's ring with a "
                   "float32 stream")},
        counts="eva_layers"),
    "kda": Mixer(
        group="kda",
        # Heads over tp; the output norm's one weight whole.
        specs=lambda cfg: {
            "k_wqkv": P("pp", None, None, None, "tp"),
            "k_conv": P("pp", None, None, None, "tp"),
            "k_norm": P("pp"),
            **{k: P("pp", None, None, "tp") for k in (
                "k_wf", "k_wbeta", "k_wg")},
            **{k: P("pp", None, "tp") for k in ("k_fb", "k_A", "k_wo")}},
        init=lambda cfg, rng, lead, norm: _init_kda(
            cfg, jax.random.fold_in(rng, 6), lead, norm),
        mixer=lambda cfg, h, lp, seg, gathered_seg: _kda_mixer(cfg, h, lp),
        check=_check_kda,
        refuses=lambda cfg: {
            "packed": ("packed documents through a kda layer are not "
                       "built: the scan's state and the convolutions are "
                       "not reset at a segment boundary"),
            "sp": ("sequence shards (sp > 1) through a kda layer are not "
                   "built: the scan's state at a shard's last token and "
                   f"the convolutions' last {cfg.kda_conv - 1} rows are "
                   "not handed to the next sp member"),
            "pp": ("pipeline stages (pp > 1) through a kda layer are not "
                   "built: no test holds the scan's kernels on the "
                   "pipeline's ring")},
        counts="kda_layers"),
}
LAYER_KINDS = tuple(MIXERS)


def _refuse(cfg: TransformerConfig, what: str) -> None:
    """Raise if ``what`` ("packed" documents, "sp" > 1 or "pp" > 1) is not
    built through a model of ``cfg``, with the sentence of the first to
    refuse it: its kinds in order, a multi-token-prediction module, the
    router's carried state, its balancing bias, its prediction heads."""
    refused = [MIXERS[kind].refuses(cfg)
               for kind in dict.fromkeys(cfg.mixer_kinds)]
    if cfg.n_mtp_modules:
        refused.append(_LATENT_REFUSES)
    if cfg.router_hidden:
        refused.append(_CCA_ACROSS)
    if cfg.expert_bias_rate:
        refused.append({"sp": (
            "the router's balancing bias is not built over sp > 1: no test "
            "holds the counts of a sequence's shards")})
    if cfg.n_pred_heads > 1:
        refused.append({
            "packed": ("packed documents under n_pred_heads > 1 are not "
                       "built: a head's shifted labels would cross a "
                       "segment's end"),
            "sp": ("n_pred_heads > 1 is not built over sp > 1: a head's "
                   "labels are shifted along the whole sequence")})
    for refuses in refused:
        if what in refuses:
            raise ValueError(refuses[what])


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _router_mlp(r, lp_norm, weights, eps):
    """``gelu(gelu(rms(r) W_1) W_2) W_3`` of the router's state r [b, t,
    R], float32 at the highest matmul precision."""
    w1, w2, w3 = weights
    dot = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    y = r * jax.lax.rsqrt(jnp.mean(jnp.square(r), -1, keepdims=True)
                          + eps) * lp_norm
    y = jax.nn.gelu(dot("btr,rs->bts", y, w1))
    y = jax.nn.gelu(dot("btr,rs->bts", y, w2))
    return dot("btr,re->bte", y, w3)


@jax.named_scope("zaya_router")
def _zaya_router(cfg: TransformerConfig, h, lp, r_prev):
    """The ZAYA router of the module's docstring on the block's normed h
    [b, t, d] with the layer before's state ``r_prev`` [b, t, R] float32:
    (router logits float32 [b, t, E], this layer's state)."""
    # float32 in earnest, as the linear router's matmul (parallel/moe.py).
    r = jnp.einsum("btd,dr->btr", h.astype(jnp.float32), lp["r_down"],
                   precision=lax.Precision.HIGHEST) + lp["r_gamma"] * r_prev
    logits = _router_mlp(r, lp["r_norm"],
                         (lp["r_w1"], lp["r_w2"], lp["r_w3"]), cfg.norm_eps)
    return logits, r


@jax.checkpoint
def _scaled_residual(x, out, scales):
    """``a * x + b + c * out`` with ``scales`` = (a, b, c) [3, d] float32,
    in float32; the stream keeps its type."""
    a, b, c = scales
    return (a * x.astype(jnp.float32) + b
            + c * out.astype(jnp.float32)).astype(x.dtype)


def _times(x, multiplier):
    """``x * multiplier``; a multiplier of one is no instruction."""
    return x if multiplier == 1.0 else x * multiplier


def _residual(x, out, multiplier):
    """A block's result joins the stream: in the stream's type, float32
    under ``float32_stream`` whatever the block computed in."""
    return x + _times(out, multiplier)


def _plus(x, offset: int):
    """``x + offset``; an offset of zero is no instruction."""
    return x + offset if offset else x


def _runs(pattern):
    """The maximal runs of one (mixer, feed-forward) pair in ``pattern``,
    in order: (mixer, feed-forward, the run's first row in each group of
    stacks, length). The rows are by ``_leaf_groups``: None the run's first
    layer, the mixer's and the feed-forward's group how many earlier
    layers read that group."""
    runs, seen = [], {}
    for at, (mixer, ffn) in enumerate(pattern):
        groups = (MIXERS[mixer].group, ffn)
        if runs and runs[-1][:2] == [mixer, ffn]:
            runs[-1][3] += 1
        else:
            runs.append([mixer, ffn, {None: at, **{
                g: seen.get(g, 0) for g in groups}}, 1])
        for g in groups:
            seen[g] = seen.get(g, 0) + 1
    return [tuple(run) for run in runs]


def _rows(stack, first, n):
    """Layers ``first .. first + n`` of a stack; the whole stack is
    itself, no slice."""
    if first == 0 and n == stack.shape[0]:
        return stack
    return lax.slice_in_dim(stack, first, first + n, axis=0)


class Carry(NamedTuple):
    """What rides beside the activations from stage to stage; a member a
    model does not have is None (no leaf). The pipeline's ring carries all
    four; a stage's layer scans carry ``x`` and ``state`` (the ids are the
    scans' constant, the statistics their result)."""
    x: Any  # the activations [mb, t_local, d]
    seg: Any = None  # segment ids int32 [mb, t_local] (packed documents)
    stats: Any = None  # ``_zero_router_stats`` (``cfg.use_moe``)
    state: Any = None  # the router's, float32 [mb, t_local, R]


def _make_layer_fn(cfg: TransformerConfig, packed: bool = False):
    """layer(kind, ffn, carry, lp, seg, gathered_seg, experts): one layer,
    its mixer block of ``kind`` then its feed-forward block ``ffn``, on
    ``Carry(x [mb, t_local, d], state=the router's of the layer before)``
    with the layer's leaves ``lp``: ``(the carry after the layer, an
    expert layer's statistics or None)``. ``experts`` = (the stacks an
    expert layer's matrices lie in, its index in them). Rematerialized
    with ``cfg.remat``."""
    norm = _block_norm(cfg)
    if packed:
        _refuse(cfg, "packed")

    def layer(kind, ffn, carry, lp, seg, gathered_seg, experts):
        with jax.named_scope(kind):
            x = mixer_block(kind, carry.x, lp, seg, gathered_seg)
        with jax.named_scope(ffn):
            x, state, stats = feed_forward_block(ffn, x, lp, experts,
                                                 carry.state)
        return carry._replace(x=x, state=state), stats

    def joined(x, out, lp, scales):
        if cfg.residual_scales:
            return _scaled_residual(x, out, lp[scales])
        return _residual(x, out, cfg.residual_multiplier)

    def mixer_block(kind, x, lp, seg, gathered_seg):
        h = norm(x, lp["ln1"])
        out = MIXERS[kind].mixer(cfg, h, lp, seg, gathered_seg)
        out = lax.psum(out, "tp")  # combine head shards
        if cfg.post_norms:
            out = norm(out, lp["ln1_post"])
        return joined(x, out, lp, "res1")

    def gated_mlp(h, wgu, w2):
        gu = checkpoint_name(jnp.einsum("btd,dcf->btcf", h, wgu), "mlp_gu")
        y = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
        return jnp.einsum("btf,fd->btd", y, w2)

    def dense_mlp(h, lp):
        if cfg.gated_mlp:
            return gated_mlp(h, lp["wgu"], lp["w2"])
        y = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lp["w1"]))
        return jnp.einsum("btf,fd->btd", y, lp["w2"])

    def mlp_by_blocks(h, lp):
        """``dense_mlp`` by blocks of ``cfg.mlp_block`` tokens."""
        b, t, d = h.shape
        one = jax.checkpoint(lambda rows: dense_mlp(rows[None], lp)[0])
        _, y = _over_blocks(lambda carry, rows: (carry, one(rows)),
                            h.reshape(b * t, d), cfg.mlp_block, None)
        return y.reshape(b, t, d)

    def feed_forward_block(ffn, x, lp, experts, state):
        """(x after the block, the router's state after it, the expert
        layer's statistics or None)."""
        h = norm(x, lp["ln2"])
        stats = None
        if ffn == "moe":
            stacks, index = experts
            first = None if cfg.n_experts_held is None else _plus(
                lax.axis_index("dp") * lp["wg"].shape[0],
                cfg.first_expert_held)
            logits = None  # the layer's own linear router makes them
            if cfg.router_hidden:
                logits, state = _zaya_router(cfg, h, lp, state)
            y, stats = moe_layer(
                h, {k: lp[k] for k in _ROUTED_LEAVES if k in lp},
                cfg.n_experts, first, axis_name="dp", top_k=cfg.moe_top_k,
                norm_topk_prob=cfg.norm_topk_prob, seq_axis_name="sp",
                stacks=stacks, layer=index,
                score_func=cfg.moe_score_func,
                route_scale=cfg.route_scale, logits=logits,
                n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group)
            if cfg.n_shared_experts:
                with jax.named_scope("moe_shared"):
                    y = y + lax.psum(gated_mlp(
                        h, lp["shared_wgu"], lp["shared_w2"]), "tp")
        else:
            y = dense_mlp(h, lp) if cfg.mlp_block is None else \
                mlp_by_blocks(h, lp)
            y = lax.psum(y, "tp")  # combine hidden-dim shards
        if cfg.post_norms:
            y = norm(y, lp["ln2_post"])
        return joined(x, y, lp, "res2"), state, stats

    keeps = _REMAT_KEEPS if cfg.remat_keeps is None else cfg.remat_keeps
    return jax.checkpoint(
        layer, static_argnums=(0, 1),
        policy=jax.checkpoint_policies.save_only_these_names(
            *keeps)) if cfg.remat else layer


def _make_stage_fn(cfg: TransformerConfig, n_stages: int = 1,
                   packed: bool = False):
    """stage_fn(stage_params, carry) -> carry, applying this stage's
    layers (``_make_layer_fn``'s) to a ``Carry``: the ids pass through
    unchanged, the statistics gain this stage's layers, the state is the
    last layer's. Runs under the full (dp, pp, sp, tp) mesh.

    The stage walks the maximal runs of one (mixer, feed-forward) pair in
    its pattern (``cfg.stage_pattern``) and scans each over its rows of
    the stacks: the leaves every layer has by the layer's place in the
    stage, a group's own by its place among the layers of that group.
    """
    pattern = cfg.stage_pattern(n_stages)
    runs = _runs(pattern)
    groups = _leaf_groups(cfg)
    layer_fn = _make_layer_fn(cfg, packed)

    def stage_fn(stage_params, carry):
        seg, stats = carry.seg, carry.stats
        gathered = stacks = None
        if packed and cfg.sp_strategy in ("ulysses", "auto"):
            # Hoist the loop-invariant id gather out of the layer
            # scan (XLA won't lift collectives out of scan bodies);
            # if "auto" resolves to ring, the unused gather is DCE'd.
            from ..parallel.ulysses import gather_segment_ids

            gathered = gather_segment_ids(seg, "sp")
        if cfg.use_moe:
            # The expert kernels read a layer's matrices out of the
            # stage's stacks, constants of the scan, by the layer's index
            # (a Mosaic call cannot take the scan's slice without a copy
            # of it); the slices are still the leaves the scan returns
            # the weight gradients for, one layer an iteration.
            stacks = {k: lax.stop_gradient(stage_params[k])
                      for k in ("wg", "wu", "wd")}
        scanned = Carry(carry.x, state=carry.state)
        for kind, ffn, rows, n in runs:
            run_params = {
                k: _rows(v, rows[groups.get(k)], n)
                for k, v in stage_params.items()
                if groups.get(k) in rows}

            def body(scanned, layer):
                lp, index = layer
                return layer_fn(kind, ffn, scanned, lp, seg, gathered,
                                (stacks, index))

            # An expert layer's place among the stage's expert layers, for
            # the expert kernels.
            index = _plus(jnp.arange(n), rows["moe"]) if ffn == "moe" \
                else None
            scanned, layers = lax.scan(body, scanned, (run_params, index))
            if ffn != "moe":
                continue
            at = _plus(lax.axis_index("pp") * len(pattern), rows[None])
            stats = {
                "lb": stats["lb"] + jnp.sum(layers["lb"]) / cfg.n_layers,
                "z": stats["z"] + jnp.sum(layers["z"]) / cfg.n_layers,
                **{k: lax.dynamic_update_slice_in_dim(
                    stats[k], layers[k].astype(jnp.int32), at, axis=0)
                   for k in ("load", "windows")}}
        return carry._replace(x=scanned.x, stats=stats, state=scanned.state)

    return stage_fn


Forward = collections.namedtuple("Forward", "logits stats hidden")


def _spmd_forward(cfg: TransformerConfig, stage_fn, params, tokens,
                  n_microbatches: int, segment_ids=None, logits=True):
    """Shared SPMD forward (embed → pipeline → final norm → logits).

    Runs under the (dp, pp, sp, tp) mesh; tokens: local [b, t];
    ``segment_ids`` (int [b, t], sequence-sharded like tokens): packed
    sequences — microbatched alongside the activations so each pipeline
    stage masks attention for the microbatch it is holding.

    Returns a ``Forward``: ``logits``, None without ``logits`` (a loss that
    runs the head by blocks); ``stats`` (``_zero_router_stats``: the two
    loss terms as means over this member's sequences, tokens per expert
    summed over them, the most windows a microbatch took), None without
    ``cfg.use_moe``; ``hidden`` [b, t, d], the stack's output before the
    final norm."""
    b, t = tokens.shape
    with jax.named_scope("embed"):
        sp_idx = lax.axis_index("sp")
        x = _times(params["embed"][tokens],  # [b, t, d]
                   cfg.embedding_multiplier)
        if "pos" in params:  # learned positions; RoPE rotates in the layers
            pos = lax.dynamic_slice_in_dim(params["pos"], sp_idx * t, t,
                                           axis=0)
            x = x + pos[None]
        x = x.astype(jnp.float32 if cfg.float32_stream else cfg.dtype)

    # microbatch for the pipeline: [M, mb, t, d]
    M = n_microbatches
    carry = Carry(
        x=x.reshape(M, b // M, t, x.shape[-1]),
        seg=None if segment_ids is None else jnp.asarray(
            segment_ids, jnp.int32).reshape(M, b // M, t),
        stats=_zero_router_stats(cfg, (M,)) if cfg.use_moe else None,
        state=jnp.zeros((M, b // M, t, cfg.router_hidden), jnp.float32)
        if cfg.router_hidden else None)  # r_{-1} = 0
    # Per-stage params: strip the leading pp dim. The local slice MUST be
    # exactly one stage — if init_params was built with a different stage
    # count than the mesh's pp size, layers would silently be dropped.
    stage_params = {}
    for k, v in params.items():
        if k in _MODEL_LEAVES or k.startswith(_MTP):
            continue
        assert v.shape[0] == 1, (
            f"param '{k}' has {v.shape[0]} local stages; init_params "
            "n_stages must equal the mesh pp size")
        stage_params[k] = v[0]
    # The activations and the router statistics are outputs. The segment
    # ids ride the ring for the later stages but are side data, and the
    # last layer's router state is read by none.
    out = spmd_pipeline(
        stage_fn, stage_params, carry, axis_name="pp",
        collect_fn=lambda carry: carry._replace(seg=None, state=None))
    stats = out.stats
    if cfg.use_moe:
        stats = {"lb": jnp.mean(stats["lb"]), "z": jnp.mean(stats["z"]),
                 "load": jnp.sum(stats["load"], axis=0),
                 "windows": jnp.max(stats["windows"], axis=0)}
    y = out.x.reshape(b, t, -1)
    return Forward(
        _head(cfg, params, y, params["final_ln"]) if logits else None,
        stats, y)


@jax.named_scope("head")
def _head(cfg: TransformerConfig, params, y, final_ln):
    """float32 logits [b, t, V] of hidden states y [b, t, d]: the norm
    with weight ``final_ln``, then the head (or the tied table). With
    ``cfg.n_pred_heads`` = P > 1 [b, t, P, V], a head's logits a row."""
    y = _block_norm(cfg)(y, final_ln).astype(jnp.float32)
    tied = cfg.tie_embeddings
    logits = jnp.einsum("btd,vd->btv" if tied else "btd,dv->btv", y,
                        params["embed" if tied else "head"].astype(
                            jnp.float32))
    if cfg.n_pred_heads > 1:
        logits = logits.reshape(logits.shape[:2] + (cfg.n_pred_heads, -1))
    return _times(logits, 1.0 / cfg.logits_scaling)


def _mtp_module(cfg: TransformerConfig, layer_fn, params, hidden, inputs,
                targets):
    """The multi-token-prediction module of the module's docstring on the
    stack's output ``hidden`` [b, t, d], the tokens it embeds ``inputs``
    = ``t_{i+1}`` and those it predicts ``targets`` = ``t_{i+2}`` [b, t]
    (whole sequences): ``(cross-entropy of the targets per token, float32
    [b, t], zero at a sequence's last position, which has no target; the
    mean over the others; its layer's router statistics or None)``."""
    norm = _block_norm(cfg)
    lp = {k[len(_MTP):]: v[0] for k, v in params.items()
          if k.startswith(_MTP)}
    kind, ffn = cfg.mtp_kind, cfg.ffn_kinds[-1]
    with jax.named_scope("embed"):
        emb = _times(params["embed"][inputs],
                     cfg.embedding_multiplier).astype(cfg.dtype)
        g = jnp.einsum("btc,cd->btd", jnp.concatenate([
            norm(hidden, lp["hnorm"]), norm(emb, lp["enorm"])], -1),
            lp["eh"])
    stacks = {k: lax.stop_gradient(params[_MTP + k])
              for k in ("wg", "wu", "wd")} if ffn == "moe" else None
    carry, stats = layer_fn(kind, ffn, Carry(g), lp, None, None, (stacks, 0))
    logits = _head(cfg, params, carry.x, lp["final_ln"])
    with jax.named_scope("loss"):
        nll = token_nll(logits, targets).at[:, -1].set(0.0)
        return nll, jnp.sum(nll) / (nll.size - nll.shape[0]), stats


@jax.custom_vjp
def token_nll(logits, labels):
    """Cross-entropy per token, ``-log_softmax(logits)[label]`` as float32
    [b, t], from float32 logits [b, t, V] and int labels [b, t], without
    the [b, t, V] log-probabilities: the forward pass reads the logits
    (max, then ``log sum exp(logits - max)``, as ``jax.nn.log_softmax``
    computes them) and picks b*t of them; the backward pass is one
    elementwise ``softmax - onehot`` that XLA fuses into the head's two
    matmuls. The log-sum-exp is kept in its two parts, max and log-sum, so
    logits of any magnitude keep the precision ``log_softmax`` has."""
    return _token_nll_fwd(logits, labels)[0]


def _token_nll_fwd(logits, labels):
    top = jnp.max(logits, axis=-1)
    log_sum = jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return log_sum - (picked - top), (logits, top, log_sum, labels)


def _token_nll_bwd(residuals, g):
    logits, top, log_sum, labels = residuals
    probs = jnp.exp(logits - top[..., None] - log_sum[..., None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    return (probs - onehot) * g[..., None], None


token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def _block_logits(y, table, tied, scale):
    """float32 logits [n, V] of a block's normed float32 hidden states y
    [n, d] under ``table`` ([V, d] with ``tied``, else [d, V])."""
    with jax.named_scope("head_block"):
        logits = jnp.einsum("nd,vd->nv" if tied else "nd,dv->nv", y,
                            table.astype(jnp.float32))
        return _times(logits, scale)


def _over_blocks(one, rows, block, carry):
    """``one(carry, rows of a block) -> (carry, what the block gives)``
    over consecutive blocks of ``block`` rows of every leaf of ``rows``
    [N, ...], the whole blocks in a ``lax.scan``, what is left of N after
    them as one shorter block: (carry, the blocks' results joined)."""
    n = jax.tree.leaves(rows)[0].shape[0]
    whole = n // block
    outs = []
    if whole:
        carry, out = lax.scan(one, carry, jax.tree.map(
            lambda a: a[:whole * block].reshape(
                (whole, block) + a.shape[1:]), rows))
        outs.append(jax.tree.map(
            lambda a: a.reshape((whole * block,) + a.shape[2:]), out))
    if n % block:
        carry, out = one(carry, jax.tree.map(lambda a: a[whole * block:],
                                             rows))
        outs.append(out)
    return carry, jax.tree.map(lambda *parts: jnp.concatenate(parts), *outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def block_nll(y, table, labels, weights, block, tied, scale):
    """The weighted sum of ``token_nll`` of the logits ``scale * y table``
    without them, and every token's: ``(sum_i weights[i] * nll[i], nll)``,
    a float32 scalar and float32 [N], of normed float32 hidden states y
    [N, d] under ``table`` ([V, d] with ``tied``, the embedding table
    itself, else [d, V]), int labels [N] and the float32 ``weights`` [N]
    the loss gives the tokens (a mean's ``1 / N``), by blocks of ``block``
    tokens. Only the sum takes a gradient: ``nll`` is there to be read,
    and a cotangent of it is dropped.

    A block's float32 logits are formed once (``head_block``) and each
    token's max, log-sum and picked logit read from them (``loss_block``).
    Where the sum is differentiated, the forward pass makes the gradients
    beside them, from the same logits: ``weights * (softmax - onehot)``
    (``loss_block``), and from it the block's rows of the hidden states'
    gradient and its term of the table's, summed in float32 over the
    blocks (``head_block``). The weights are an argument because that
    needs every token's cotangent before a backward pass exists; the
    backward pass multiplies the two kept gradients by the sum's
    cotangent, a scalar. Where nothing is differentiated no gradient is
    made. No [N, V] array exists in either pass."""
    return _head_blocks(y, table, labels, weights, block, tied, scale,
                        gradients=False)[0]


def _head_blocks(y, table, labels, weights, block, tied, scale, gradients):
    """``block_nll``'s result by blocks of ``block`` tokens and, with
    ``gradients``, ``(d_y [N, d], d_table, float32)`` of its sum; else
    None."""
    def one(d_table, rows):
        y, labels, weights = rows
        logits = _block_logits(y, table, tied, scale)
        with jax.named_scope("loss_block"):
            top = jnp.max(logits, axis=-1)
            log_sum = jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), -1))
            picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            nll = log_sum - (picked - top)
            if not gradients:
                return d_table, (nll, None)
            probs = jnp.exp(logits - top[:, None] - log_sum[:, None])
            onehot = jax.nn.one_hot(labels, logits.shape[-1],
                                    dtype=logits.dtype)
            d_logits = _times((probs - onehot) * weights[:, None], scale)
        with jax.named_scope("head_block"):
            table32 = table.astype(jnp.float32)
            if tied:
                d_y = jnp.einsum("nv,vd->nd", d_logits, table32)
                d_table = d_table + jnp.einsum("nv,nd->vd", d_logits, y)
            else:
                d_y = jnp.einsum("nv,dv->nd", d_logits, table32)
                d_table = d_table + jnp.einsum("nd,nv->dv", y, d_logits)
        return d_table, (nll, d_y)

    d_table, (nll, d_y) = _over_blocks(
        one, (y, labels, weights), block,
        jnp.zeros(table.shape, jnp.float32) if gradients else None)
    return (jnp.sum(weights * nll), nll), (
        (d_y, d_table) if gradients else None)


def _block_nll_fwd(y, table, labels, weights, block, tied, scale):
    # Once a differentiated trace, never in an evaluation's.
    _metrics.inc("head.blocks_with_gradients_traced")
    out, (d_y, d_table) = _head_blocks(y, table, labels, weights, block,
                                       tied, scale, gradients=True)
    return out, (d_y, d_table, out[1], table)


def _block_nll_bwd(block, tied, scale, residuals, cotangents):
    d_y, d_table, nll, table = residuals
    g, _ = cotangents  # of the sum; the per-token output takes none
    return (g * d_y, (g * d_table).astype(table.dtype), None, g * nll)


block_nll.defvjp(_block_nll_fwd, _block_nll_bwd)


@jax.named_scope("head")
def _head_nll(cfg: TransformerConfig, params, y, labels):
    """``token_nll(_head(y), labels)`` [b, t] and its mean, by blocks of
    ``cfg.head_block`` tokens (``block_nll``): ``(mean, [b, t])``."""
    b, t, d = y.shape
    y = _block_norm(cfg)(y, params["final_ln"]).astype(jnp.float32)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    mean, nll = block_nll(
        y.reshape(b * t, d), table, labels.reshape(b * t),
        jnp.full(b * t, 1.0 / (b * t), jnp.float32), cfg.head_block,
        cfg.tie_embeddings, 1.0 / cfg.logits_scaling)
    return mean, nll.reshape(b, t)


def make_loss_fn(cfg: TransformerConfig, mesh, n_microbatches: int = 2,
                 packed: bool = False, with_readings: bool = False):
    """Build loss(params, tokens, labels) -> scalar, shard_mapped over the
    full mesh. tokens/labels: [B_global, T_global] sharded P('dp','sp').
    The ``loss`` scope covers ``token_nll``'s forward pass and its
    hand-written backward pass; with ``cfg.head_block`` the mean
    cross-entropy is ``block_nll``'s weighted sum, under ``head``, and
    its gradients are made in the forward pass.

    ``with_readings`` returns ``(loss, readings)`` instead:
    ``readings["token_nll"]`` float32 [B, T] every token's cross-entropy,
    sharded as the tokens, whose mean the loss is ([B, T, P] with
    ``cfg.n_pred_heads`` = P > 1, a head's a column); and of a
    ``cfg.use_moe`` model ``readings["load"]`` int32 [n_layers, n_experts]
    the tokens of the global batch that chose each expert in each layer
    (a dense layer's row is zero), what the train step moves the
    balancing bias by, ``readings["windows"]`` int32 [n_layers] the most
    windows of the sorted assignments an expert layer took on any member
    (``parallel.moe``: 1 where the held experts' rows fit one; a dense
    layer's entry is zero). With ``cfg.n_mtp_modules`` both ``load`` and ``windows``
    have one more row, the module's layer after the stack's, and
    ``readings["mtp_token_nll"]`` [B, T] is every token's cross-entropy
    of the token after the next in the module (zero at a sequence's last
    position), whose mean over the other positions the loss adds
    ``cfg.mtp_loss_weight`` times (the module's docstring).

    With ``cfg.use_moe`` the loss is the mean cross-entropy plus
    ``router_aux_loss_coef`` times the load-balance term and
    ``router_z_loss_coef`` times the z-loss, each a mean over layers and
    sequences (``parallel.moe.moe_layer``).

    ``packed=True`` builds loss(params, tokens, labels, segment_ids)
    instead: attention masks within segments (packed sequences). The
    loss itself stays plain mean cross-entropy — mask cross-segment
    next-token positions through the labels (e.g. weight-zero ids) as
    your data pipeline defines them."""
    _validate_mesh_divisibility(cfg, mesh)
    stage_fn = _make_stage_fn(cfg, _pipeline_stages(mesh), packed=packed)
    mtp_layer_fn = _make_layer_fn(cfg, packed) if cfg.n_mtp_modules else None
    specs = _param_specs(cfg)

    def spmd_loss(params, tokens, labels, segment_ids=None):
        out = _spmd_forward(
            cfg, stage_fn, params, tokens, n_microbatches,
            segment_ids=segment_ids, logits=cfg.head_block is None)
        stats = out.stats
        if cfg.n_mtp_modules:
            with jax.named_scope("mtp"):
                mtp_nll, mtp_loss, mtp_stats = _mtp_module(
                    cfg, mtp_layer_fn, params, out.hidden, labels,
                    jnp.roll(labels, -1, axis=1))
        if cfg.head_block is not None:
            loss, nll = _head_nll(cfg, params, out.hidden, labels)
        with jax.named_scope("loss"):
            if cfg.head_block is None:
                if cfg.n_pred_heads > 1:
                    # Head j's labels: the labels shifted by j more.
                    labels = jnp.stack([
                        jnp.roll(labels, -j, axis=1)
                        for j in range(cfg.n_pred_heads)], -1)
                nll = token_nll(out.logits, labels)
                loss = jnp.mean(nll)
            if cfg.use_moe:
                loss = (loss + cfg.router_aux_loss_coef * stats["lb"]
                        + cfg.router_z_loss_coef * stats["z"])
            if cfg.n_mtp_modules:
                loss = loss + cfg.mtp_loss_weight * mtp_loss
            loss = lax.pmean(loss, ("dp", "sp"))
        if not with_readings:
            return loss
        if cfg.n_mtp_modules and mtp_stats:
            # The module's layer after the stack's: one more row.
            stats = {k: jnp.concatenate([stats[k], mtp_stats[k].astype(
                jnp.int32)[None]]) for k in ("load", "windows")}
        readings = {"token_nll": nll}
        if cfg.use_moe:
            readings.update(
                load=lax.psum(stats["load"], ("dp", "sp")),
                windows=lax.pmax(stats["windows"], ("dp", "sp")))
        if cfg.n_mtp_modules:
            readings["mtp_token_nll"] = mtp_nll
        return loss, readings

    data = P("dp", "sp")
    in_specs = (specs,) + (data,) * (3 if packed else 2)
    out_readings = {"token_nll": data}
    if cfg.use_moe:
        out_readings.update(load=P(), windows=P())
    if cfg.n_mtp_modules:
        out_readings["mtp_token_nll"] = data
    # Around the shard_map, so that every instruction of the pass carries
    # jvp(forward), and transpose(jvp(forward)) in the backward pass.
    return jax.named_scope("forward")(_compat_shard_map(
        spmd_loss, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), out_readings) if with_readings else P(),
        check_vma=False))


@_metrics.span("step.build")
def make_train_step(cfg: TransformerConfig, optimizer, mesh,
                    n_microbatches: int = 2, opt_shardings=None,
                    packed: bool = False, with_readings: bool = False):
    """Full sharded training step: loss + grads + optimizer update, jitted
    once over the 4-axis mesh.

    ``opt_shardings`` (a pytree of NamedShardings matching the optimizer
    state, e.g. ``jax.tree.map(lambda x: x.sharding, opt_state)`` from a
    ``training.init_opt_state(..., zero_axis="dp")`` state) pins the
    updated optimizer state to those shardings inside the compiled
    program — the ZeRO-1 composition: moments stay partitioned over dp
    on top of the params' tp/pp sharding, and XLA inserts the
    slice/gather collectives around the elementwise update.

    ``packed=True`` builds step(params, opt_state, tokens, labels,
    segment_ids) for packed-sequence training (``make_loss_fn``).

    The step holds the leaves that are no trained parameter aside
    (``trained``'s complement; most models have none): they take no
    gradient and the optimizer never sees them (``opt_state`` is made for
    ``trained(params)``). With ``with_readings`` the step returns
    ``(params, opt_state, loss, readings)``, ``readings`` the loss
    function's of this step's forward pass (``make_loss_fn``); a step
    with ``cfg.expert_bias_rate`` always does: its held leaves are
    ``params["expert_bias"]``, which it moves from the tokens each
    expert got in this very step, under the scope ``router_bias``. A
    multi-token-prediction module's layer has a bias of its own,
    ``params["mtp_expert_bias"]``, moved the same way from the last row
    of ``readings["load"]``."""
    import optax

    counts = _model_counts(cfg)
    for name, n in counts.items():
        _metrics.inc(f"model.{name}", n)
    _metrics.note(**counts)
    biased = bool(cfg.expert_bias_rate)
    with_readings = with_readings or biased
    if biased and packed:
        raise ValueError("packed documents with the router's balancing "
                         "bias are not built")
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches, packed=packed,
                           with_readings=with_readings)

    @jax.named_scope("optimizer")
    def apply(grads, params, opt_state):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        if opt_shardings is not None:
            opt_state = jax.lax.with_sharding_constraint(
                opt_state, opt_shardings)
        return optax.apply_updates(params, updates), opt_state

    @jax.named_scope("router_bias")
    def move(biases, load):
        # The expert layers' rows (they follow the dense ones), as each
        # bias is stacked: [S, L, E]; a multi-token-prediction module's
        # layer [M, E] follows the stack's.
        rows = {"expert_bias": slice(cfg.num_dense_layers, cfg.n_layers),
                "mtp_expert_bias": slice(cfg.n_layers, None)}
        return {k: update_expert_bias(
            bias, load[rows[k]].reshape(bias.shape), cfg.expert_bias_rate)
            for k, bias in biases.items()}

    def step(params, opt_state, tokens, labels, *segment_ids):
        weights = trained(params)
        held = {k: v for k, v in params.items() if k not in weights}
        out, grads = jax.value_and_grad(
            lambda weights: loss_fn({**weights, **held}, tokens, labels,
                                    *segment_ids),
            has_aux=with_readings)(weights)
        weights, opt_state = apply(grads, weights, opt_state)
        if not with_readings:
            return weights, opt_state, out
        loss, readings = out
        if biased:
            held = move(held, readings["load"])
        return {**weights, **held}, opt_state, loss, readings

    # The module's name on the device trace (docs/diagnostics.md,
    # "Tracing"); a step with a bias has always had its own there.
    step.__name__ = step.__qualname__ = (
        "hvd_decoder_bias_step" if biased else "hvd_decoder_step")
    return jax.jit(step, donate_argnums=(0, 1))


def update_expert_bias(bias, load, rate: float):
    """Aux-loss-free balancing (Wang et al., arXiv:2408.15664), centred:
    ``bias + delta - mean(delta)`` with ``delta = rate * sign(mean(load) -
    load)`` over each layer's experts; bias float32 [..., E], load the
    tokens each expert got, same shape."""
    load = load.astype(jnp.float32)
    delta = rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return bias + delta - jnp.mean(delta, -1, keepdims=True)


def dense_reference_loss(cfg: TransformerConfig, params, tokens, labels,
                         segment_ids=None):
    """Unsharded single-device oracle of the dense LayerNorm decoder:
    mathematically identical to the sharded loss (pipeline == sequential
    layers; ring attention == dense causal attention). Used by tests to
    validate sharded loss AND gradients. The MoE, RMSNorm and QK-norm
    variants are held to ``benchmark/reference_moe.py`` instead, and
    everything the Mamba-2 hybrid brought (a layer pattern, the gated
    MLP, a tied head, no positions, the multipliers) to
    ``benchmark/reference_hybrid.py``, and sliding and full attention
    layers, the gate, per-head QK-norm, post-norms, the sigmoid router
    with its bias, held experts and the shared expert to
    ``benchmark/reference_afmoe.py``, and latent attention and the
    multi-token-prediction module to ``benchmark/reference_glm_lite.py``."""
    if (cfg.use_moe or cfg.norm != "layernorm" or cfg.qk_norm
            or set(cfg.kinds) != {LAYER_KINDS[0]} or cfg.attn_gate
            or cfg.post_norms or cfg.gated_mlp or cfg.tie_embeddings
            or not (cfg.pos_table or cfg.rope)
            or (cfg.embedding_multiplier, cfg.residual_multiplier,
                cfg.logits_scaling, cfg.attention_multiplier)
            != (1.0, 1.0, 1.0, None)):
        raise ValueError("dense_reference_loss covers the dense LayerNorm "
                         "decoder only; see benchmark/reference_moe.py, "
                         "benchmark/reference_hybrid.py, "
                         "benchmark/reference_afmoe.py and "
                         "benchmark/reference_glm_lite.py")
    from ..parallel.ring_attention import local_flash_attention

    def attend(q, k, v):
        if segment_ids is None and cfg.attention_window is None:
            return local_flash_attention(q, k, v, causal=True)
        T = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / jnp.sqrt(
            jnp.asarray(q.shape[-1], jnp.float32))
        iq = jnp.arange(T)[:, None]
        ik = jnp.arange(T)[None, :]
        allowed = (iq >= ik)[None, None]
        if cfg.attention_window is not None:
            allowed = allowed & (iq - ik < cfg.attention_window)[None, None]
        if segment_ids is not None:
            seg = jnp.asarray(segment_ids)
            allowed = allowed & (seg[:, None, :, None]
                                 == seg[:, None, None, :])
        s = jnp.where(allowed, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    b, t = tokens.shape
    x = params["embed"][tokens]
    if "pos" in params:
        x = x + params["pos"][:t][None]
    x = x.astype(cfg.dtype)
    n_stages, lps = params["ln1"].shape[:2]

    for s in range(n_stages):
        for li in range(lps):
            h = _layernorm(x, params["ln1"][s, li])
            if "wqkv" in params:
                qkv = jnp.einsum("btd,dchk->btchk", h,
                                 params["wqkv"][s, li])
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                q = jnp.einsum("btd,dhk->bthk", h, params["wq"][s, li])
                kv = jnp.einsum("btd,dchk->btchk", h,
                                params["wkv"][s, li])
                k, v = kv[:, :, 0], kv[:, :, 1]
            if cfg.rope:
                pos = jnp.arange(t, dtype=jnp.int32)
                q = _rope(q, pos, cfg.rope_theta)
                k = _rope(k, pos, cfg.rope_theta)
            if k.shape[2] != q.shape[2]:
                g = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, g, axis=2)
                v = jnp.repeat(v, g, axis=2)
            attn = attend(q, k, v)
            x = x + jnp.einsum("bthk,hkd->btd", attn, params["wo"][s, li])
            h = _layernorm(x, params["ln2"][s, li])
            y = jax.nn.gelu(jnp.einsum(
                "btd,df->btf", h, params["w1"][s, li]))
            x = x + jnp.einsum("btf,fd->btd", y, params["w2"][s, li])

    x = _layernorm(x, params["final_ln"])
    logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                        params["head"].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -jnp.mean(ll)


def make_forward_fn(cfg: TransformerConfig, mesh, n_microbatches: int = 2):
    """Inference forward returning logits, sharded like the loss."""
    stage_fn = _make_stage_fn(cfg, _pipeline_stages(mesh))
    specs = _param_specs(cfg)

    def spmd_fwd(params, tokens):
        return _spmd_forward(cfg, stage_fn, params, tokens,
                             n_microbatches).logits

    return jax.jit(_compat_shard_map(
        spmd_fwd, mesh=mesh,
        in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp"), check_vma=False))


def make_router_load_fn(cfg: TransformerConfig, mesh,
                        n_microbatches: int = 2):
    """Jitted load(params, tokens) -> int32 [n_layers, n_experts]: how
    many of the global batch's tokens chose each expert in each MoE
    layer, held here or not. Every expert layer's row sums to
    ``moe_top_k`` times the tokens: nothing is dropped; a dense layer's
    row is zero. A program of its own, not an output of the training step
    (the step of a model with a balancing bias returns its own counts)."""
    stage_fn = _make_stage_fn(cfg, _pipeline_stages(mesh))
    specs = _param_specs(cfg)

    def spmd_load(params, tokens):
        stats = _spmd_forward(cfg, stage_fn, params, tokens,
                              n_microbatches).stats
        return lax.psum(stats["load"], ("dp", "sp"))

    return jax.jit(_compat_shard_map(
        spmd_load, mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P(), check_vma=False))
