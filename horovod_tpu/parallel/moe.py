"""Dropless mixture-of-experts layer: top-k routing, gated SiLU experts.

Every token reaches all ``top_k`` of its experts; nothing has a capacity
and nothing is dropped. The ``top_k * N`` assignments are sorted by
expert, the tokens' rows gathered in that order, and the experts run as
three grouped matmuls over the ragged groups (bf16 operands where the
parameters are bf16, float32 accumulation on the MXU) whose sizes are
data, so every shape is static: on the chip jax's Pallas grouped matmul
(``megablox``) under the scope ``moe_gmm``, elsewhere XLA's
``lax.ragged_dot``, by the attention kernels' own rule
(``ops.pallas_attention._resolve_dispatch``). The kernels read a layer's
matrices where they lie in a stack of layers (``moe_layer``'s ``stacks``),
so a scan over layers copies none out for them. The rows go back to their
tokens by the inverse permutation, already under the router's weights,
and are summed. Dispatch and combine are gathers in both directions (the
transpose of a permutation gather is the gather by its inverse), never a
one-hot and never a scatter.

The layer is told how many experts the router scores (``n_experts``) and
which of them it holds (``first`` and the leading size of its matrices).
It routes over all of them and computes the part of the result its own
experts give: the held experts' groups come first in the sort, the
assignments that fell on other experts after them, which no group
covers, and their rows are zeroed. A chip that holds a share of a
deployment's experts and runs without its fellows gives that partial
result as it is; nothing stands in for the absent ones.

Expert parallelism rides ``axis_name`` (the ``dp`` mesh axis): each of
its ``ep`` members holds ``E / ep`` experts. The same code runs on every
member over the all-gathered tokens, and the partial results are
reduce-scattered back; at ``ep`` 1 both collectives are the identity.

The router scores by softmax (the loss terms ``lb`` and ``z`` are its) or
by sigmoid; an ``expert_bias`` moves which experts a token picks and
never their weights (aux-loss-free balancing: the caller moves it by the
``load`` this layer returns).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..common.compat import axis_size as _axis_size
from ..ops import pallas_attention as _pallas_attention

# Largest (rows, contraction, columns) tile of the Pallas grouped matmul
# by operand item size: what fits the kernel's fast memory on a v5e, and
# of the tilings tried on the chip at [65536, 2048] x [64, 2048, 1024] the
# fastest (PERF.md, PR 26).
_GMM_TILE_CAPS = {2: (512, 1024, 1024), 4: (512, 512, 512)}


def init_moe_params(rng, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32):
    kr, kg, ku, kd = jax.random.split(rng, 4)

    def normal(key, shape, scale, dt):
        return (jax.random.normal(key, shape) * scale).astype(dt)

    return {
        "router": normal(kr, (d_model, n_experts), d_model ** -0.5,
                         jnp.float32),
        "wg": normal(kg, (n_experts, d_model, d_ff), d_model ** -0.5, dtype),
        "wu": normal(ku, (n_experts, d_model, d_ff), d_model ** -0.5, dtype),
        "wd": normal(kd, (n_experts, d_ff, d_model), d_ff ** -0.5, dtype),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, fan):
    """``x[index // fan]`` for a permutation ``index`` of ``fan *
    len(x)`` with inverse ``inverse``. Its transpose is the gather by the
    inverse, summed over each row's ``fan`` copies."""
    return x[index // fan]


def _take_rows_fwd(x, index, inverse, fan):
    return x[index // fan], inverse


def _take_rows_bwd(fan, inverse, g):
    return g[inverse].reshape(-1, fan, g.shape[-1]).sum(1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _gmm_tiling(lhs, stack):
    """The kernels' (rows, contraction, columns) tile of ``lhs`` [m, k] by
    ``stack`` [L, g, k, n]: one tiling for the product and both of its
    gradients."""
    sizes = (lhs.shape[0], lhs.shape[1], stack.shape[3])
    caps = _GMM_TILE_CAPS[lhs.dtype.itemsize]
    return tuple(math.gcd(size, cap) for size, cap in zip(sizes, caps))


def _gmm_of_layer(lhs, stack, layer, group_sizes, tiling, transpose_rhs,
                  interpret):
    """The Pallas grouped matmul of ``lhs`` with the matrices
    ``stack[layer]``, read where they lie: the kernel takes the whole
    stack as ``L * g`` groups of which only this layer's have rows, and
    its index map, which skips empty groups, finds a tile's matrix at
    ``layer * g`` + its group. A slice of the stack would have to be
    copied out first: a custom call's operand is a buffer of its own."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    n_layers, groups = stack.shape[:2]
    sizes = lax.dynamic_update_slice(
        jnp.zeros(n_layers * groups, jnp.int32), group_sizes,
        (layer * groups,))
    return gmm(lhs, stack.reshape((-1,) + stack.shape[2:]), sizes, lhs.dtype,
               tiling, None, None, transpose_rhs, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _stacked_gmm(lhs, rhs, stack, layer, group_sizes, interpret):
    """``lhs @ stack[layer]`` by groups. ``rhs`` is ``stack[layer]`` as
    the caller's leaf: never read, it is what the weight gradient is
    returned for, [g, k, n] from this layer's groups alone, and ``stack``,
    a constant to the caller, gets none."""
    return _stacked_gmm_fwd(lhs, rhs, stack, layer, group_sizes,
                            interpret)[0]


def _stacked_gmm_fwd(lhs, rhs, stack, layer, group_sizes, interpret):
    del rhs
    out = _gmm_of_layer(lhs, stack, layer, group_sizes,
                        _gmm_tiling(lhs, stack), False, interpret)
    return out, (lhs, stack, layer, group_sizes)


def _stacked_gmm_bwd(interpret, residuals, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    lhs, stack, layer, group_sizes = residuals
    tiling = _gmm_tiling(lhs, stack)
    grad_lhs = _gmm_of_layer(grad, stack, layer, group_sizes, tiling, True,
                             interpret)
    grad_rhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, stack.dtype,
                    tiling, None, stack.shape[1], interpret=interpret)
    return grad_lhs, grad_rhs, None, None, None


_stacked_gmm.defvjp(_stacked_gmm_fwd, _stacked_gmm_bwd)


def _grouped_matmul(lhs, rhs, group_sizes, stack=None, layer=0):
    """``lhs[rows of group g] @ rhs[g]`` for consecutive groups of
    ``group_sizes`` rows of lhs [m, k], rhs [g, k, n]; rows past the last
    group come out zero. Operands as they are, float32 accumulation,
    result in the operands' type. Where ``rhs`` is ``stack[layer]`` of a
    constant ``stack`` [L, g, k, n], the kernels read it there."""
    use_pallas, interpret = _pallas_attention._resolve_dispatch(None)
    if not use_pallas:
        return lax.ragged_dot(lhs, rhs, group_sizes)
    if stack is None:
        stack = lax.stop_gradient(rhs)[None]
    with jax.named_scope("moe_gmm"):
        return _stacked_gmm(lhs, rhs, stack, layer, group_sizes, interpret)


@jax.checkpoint
def _gated(gate, up, weight):
    """``silu(gate) * up * weight[:, None]`` in float32; the backward
    pass keeps the operands in their own type and forms the float32
    values anew."""
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            * weight[:, None]).astype(gate.dtype)


def moe_layer(x, params, n_experts: int, first=None, axis_name: str = "dp",
              top_k: int = 1, norm_topk_prob: bool = False,
              seq_axis_name=None, stacks=None, layer=0,
              score_func: str = "softmax", route_scale: float = 1.0):
    """Top-k MoE over the tokens of ``x`` [B, T, d] (local sequences).

    ``params``: ``router`` [d, E] float32 (replicated) over all ``E =
    n_experts``, and the experts held here ``wg``, ``wu`` [E_held, d, f],
    ``wd`` [E_held, f, d], which are experts ``first .. first + E_held``.
    ``first`` None is the share of a member of ``axis_name`` under
    shard_map with the expert dimension sharded over it: member ``m`` of
    ``ep`` holds ``E / ep`` experts from ``m * E / ep``. A token's output
    is ``sum_j w[e_j] * (silu(u Wg[e_j]) * (u Wu[e_j])) Wd[e_j]`` over
    those of its ``top_k`` experts that are held (all of them over the
    members of ``axis_name`` together).

    ``score_func`` ``"softmax"``: the ``top_k`` largest router
    probabilities ``p``, and ``w = p`` as they are unless
    ``norm_topk_prob`` divides them by their sum. ``"sigmoid"``: scores
    ``s = sigmoid(logits)``; the ``top_k`` largest of ``s +
    params["expert_bias"]`` [E] (float32, no gradient; absent: zero) are
    picked, and ``w = route_scale * s`` of the picked, with
    ``norm_topk_prob`` over their sum (+ 1e-20) first. The sum is over
    all ``top_k`` picked experts, held or not.

    Returns ``(y [B, T, d], stats)``. ``stats["lb"]`` is the load-balance
    term ``E * sum_e f_e P_e`` of each sequence (``f_e`` the share of its
    tokens with ``e`` among their ``top_k``, ``P_e`` its mean router
    probability; ``top_k`` at perfect balance), averaged over the local
    sequences; ``stats["z"]`` the mean squared log-sum-exp of the router
    logits (both zero under ``"sigmoid"``, which has no such terms);
    ``stats["load"]`` [E] the local tokens per expert, held or not.
    ``seq_axis_name`` names the mesh axis the T axis is sharded over, if
    any, so that ``f`` and ``P`` are those of whole sequences.

    A caller that holds the experts of several layers stacked, ``wg``,
    ``wu`` [L, E_held, d, f] and ``wd`` [L, E_held, f, d], passes them
    as ``stacks`` (constants: under ``lax.stop_gradient``) with this
    layer's index ``layer``, ``params`` holding the same matrices as
    ``stacks[name][layer]``: the Pallas kernels then read the stack in
    place and no copy of a layer's matrices is made for them, and the
    gradient still goes to ``params``. Values are the same either way.
    """
    ep = _axis_size(axis_name)
    B, T, d = x.shape
    e_local = params["wg"].shape[0]
    E = n_experts
    if first is None and e_local * ep != E:
        raise ValueError(
            f"{ep} members of {axis_name!r} holding {e_local} experts each "
            f"are not the {E} the router scores; say which are held "
            f"(first)")
    if not 1 <= top_k <= E:
        raise ValueError(f"top_k={top_k} must be in [1, {E}]")
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"score_func must be 'softmax' or 'sigmoid', got "
                         f"{score_func!r}")
    # Assignments on experts that are not held here exist: their rows
    # are sorted after the held groups and zeroed.
    elsewhere = e_local < E

    with jax.named_scope("moe_route"):
        # float32 in earnest: at default precision the MXU would round
        # both operands to bf16 and the top-k with them.
        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                            params["router"],
                            precision=lax.Precision.HIGHEST)
        if score_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            biased = scores
            if "expert_bias" in params:
                biased = scores + lax.stop_gradient(params["expert_bias"])
            experts = lax.top_k(biased, top_k)[1]  # [B, T, k]
            gates = jnp.take_along_axis(scores, experts, axis=-1)
            if norm_topk_prob:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + 1e-20)
            gates = gates * route_scale
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, experts = lax.top_k(probs, top_k)  # [B, T, k]
            if norm_topk_prob:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        picked = jnp.sum(
            experts[..., None] == jnp.arange(E, dtype=experts.dtype),
            axis=(1, 2))  # [B, E]: tokens of the sequence that chose e
        if score_func == "sigmoid":
            zero = jnp.zeros((), jnp.float32)
            stats = {"lb": zero, "z": zero, "load": jnp.sum(picked, axis=0)}
        else:
            f = picked.astype(jnp.float32) / T
            p_mean = jnp.mean(probs, axis=1)
            if seq_axis_name is not None:
                f = lax.pmean(f, seq_axis_name)
                p_mean = lax.pmean(p_mean, seq_axis_name)
            stats = {
                "lb": E * jnp.mean(jnp.sum(f * p_mean, axis=-1)),
                "z": jnp.mean(jnp.square(jax.nn.logsumexp(logits,
                                                           axis=-1))),
                "load": jnp.sum(picked, axis=0),
            }

    with jax.named_scope("moe_dispatch"):
        rows = x.reshape(B * T, d)
        experts = experts.reshape(B * T * top_k)  # token-major
        gates = gates.reshape(B * T * top_k)
        if ep > 1:
            rows, experts, gates = (
                lax.all_gather(a, axis_name, tiled=True)
                for a in (rows, experts, gates))
        # The held experts first, in their order; then the others', which
        # no group below covers.
        if first is None:
            first = lax.axis_index(axis_name) * e_local
        local = (experts - first) % E
        order = jnp.argsort(local)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(e_local, dtype=local.dtype),
            axis=0, dtype=jnp.int32)
        rows = _take_rows(rows, order, inverse, top_k)  # [k N, d]
        if elsewhere:
            # Rows of experts held elsewhere: no group covers them, and a
            # grouped matmul may leave anything there, in either pass.
            mine = (local[order] < e_local)[:, None]
            rows = jnp.where(mine, rows, jnp.zeros_like(rows))

    with jax.named_scope("moe_experts"):
        # The router's weight rides the gated product, in float32, into
        # the third matmul: p (h Wd) = (p h) Wd, and the rows need no
        # weighting on their way back.
        weight = _take_rows(gates[:, None], order, inverse, 1)
        if elsewhere:
            weight = jnp.where(mine, weight, jnp.zeros_like(weight))

        def experts(lhs, name):
            return _grouped_matmul(lhs, params[name], group_sizes,
                                   stacks[name] if stacks else None, layer)

        hidden = _gated(experts(rows, "wg"), experts(rows, "wu"),
                        weight[:, 0])
        out = experts(hidden, "wd")

    with jax.named_scope("moe_combine"):
        if elsewhere:
            out = jnp.where(mine, out, jnp.zeros_like(out))
        out = _take_rows(out, inverse, order, 1)  # token-major again
        y = jnp.sum(out.reshape(-1, top_k, d), axis=1, dtype=jnp.float32)
        if ep > 1:
            y = lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                 tiled=True)
        return y.astype(x.dtype).reshape(B, T, d), stats
