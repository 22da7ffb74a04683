"""Dropless mixture-of-experts layer: top-k routing, gated SiLU experts.

Every token reaches all ``top_k`` of its experts; nothing has a capacity
and nothing is dropped. The ``top_k * N`` assignments are sorted by
expert, the tokens' rows gathered in that order, and the experts run as
three grouped matmuls over the ragged groups (bf16 operands where the
parameters are bf16, float32 accumulation on the MXU) whose sizes are
data, so every shape is static: on the chip the repository's Pallas
grouped matmul (``ops/grouped_matmul.py``) under the scope ``moe_gmm``,
elsewhere XLA's ``lax.ragged_dot``, by the attention kernels' own rule
(``ops.pallas_attention._resolve_dispatch``). The kernels read a layer's
matrices where they lie in a stack of layers (``moe_layer``'s ``stacks``),
so a scan over layers copies none out for them. The rows go back to their
tokens by the inverse permutation, already under the router's weights,
and are summed. Dispatch and combine are gathers in both directions (the
transpose of a permutation gather is the gather by its inverse), never a
one-hot over the tokens or the experts and never a scatter.

The layer is told how many experts the router scores (``n_experts``) and
which of them it holds (``first`` and the leading size of its matrices).
It routes over all of them and computes the part of the result its own
experts give: the held experts' groups come first in the sort, the
assignments that fell on other experts after them, which no group
covers. A chip that holds a share of a deployment's experts and runs
without its fellows gives that partial result as it is; nothing stands in
for the absent ones.

Only the rows the held experts use are moved. Everything from the gather
of the rows to the sum over a token's picks (``_window``) works on a
window of ``C`` consecutive positions of the sort: the rows, the two
products, the gated product, the result and their gradients are ``[C,
.]``. ``C`` is derived, not set (``_window_rows``): the least multiple of
the grouped matmul's row tile that is twice the held experts' share of
the assignments at balance, ``2 * (E_held / E) * top_k * N`` (``N`` after
the all-gather); twice, because a seeded or a trained router does not
balance (``_WINDOW_OVER_BALANCE``). The layer takes as many windows as
the held rows need, ``ceil(held rows / C)``, which is data (``stats
["windows"]``), in a ``lax.while_loop``. A routing that puts
every pick on held experts takes ``top_k * N / C`` windows and gives the
same result, slower. Where ``C`` is ``top_k * N`` or more (all experts
held, or ``ep`` up to 2) the layer is the window's body called once on
the whole sort, with no loop around it. The trip count being data,
reverse mode is written out (``_windows``): it keeps the section's inputs
alone and takes each window's ``jax.vjp`` in a second loop.

A window moves rows by gathers in both directions and never by a
scatter. Rows to their sorted positions and back (``_take_rows``, and
the gradient of the sum) are gathers of ``C`` rows. The two sums over a
token's rows in a window (the combine, and the row gradient of the
dispatch: ``_placed_sums``) are a sorted segment sum: the rows gathered
token-major, then added up, on the chip by the same Pallas ``tgmm`` with
blocks of 256 tokens as its groups and an indicator of the row's token
in its block as the other operand (``[256, C]``, never ``[N, C]``),
elsewhere by a lookup of every pick with those outside the window
masked. What a grouped matmul leaves in the rows no group covers is never
read: the sums count the held rows alone.

Expert parallelism rides ``axis_name`` (the ``dp`` mesh axis): each of
its ``ep`` members holds ``E / ep`` experts. The same code runs on every
member over the all-gathered tokens, and the partial results are
reduce-scattered back; at ``ep`` 1 both collectives are the identity.

The router scores by softmax (the loss terms ``lb`` and ``z`` are its) or
by sigmoid, of its own linear ``router`` or of logits the caller's router
made (``moe_layer``'s ``logits``: a model whose router is more than one
matrix keeps it with the model); under either an ``expert_bias`` moves
which experts a token picks and never their weights (aux-loss-free
balancing: the caller moves it by the ``load`` this layer returns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..common import metrics as _metrics
from ..common.compat import axis_size as _axis_size
from ..ops import grouped_matmul as _grouped_matmul_ops
from ..ops import pallas_attention as _pallas_attention


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


# Tokens a group of the token-side sum's grouped matmul holds: its
# indicator is [_TOKEN_BLOCK, rows], one MXU pass high.
_TOKEN_BLOCK = 256


def _placed_sums(table, place, index, count, fan, dtype):
    """[len(place) / fan, d]: for each token, a run of ``fan`` entries of
    ``place``, the sum in ``dtype`` of the rows of ``table`` its entries
    name. Only the first ``count`` rows count: an entry that names another
    adds nothing, whatever lies there. ``index`` is the other way round:
    the entry that names each row. ``count`` None: every row counts and
    ``place`` is a permutation of them.

    With a ``count`` the sums are a sorted segment sum. On the chip: the
    rows that count gathered token-major, then the Pallas ``tgmm`` with
    blocks of ``_TOKEN_BLOCK`` tokens as its groups and, as its other
    operand, which of its block's tokens each row belongs to; float32
    accumulation on the MXU, every product a row's own value. Elsewhere a
    lookup of every entry with those that add nothing masked."""
    rows, d = table.shape
    tokens = len(place) // fan
    if count is None:
        return table[place].reshape(tokens, fan, d).sum(1, dtype=dtype)
    use_pallas, interpret = _pallas_attention._resolve_dispatch(None)
    if use_pallas and d % 128 == 0 and tokens % _TOKEN_BLOCK == 0:
        # The rows that do not count last, past every token.
        at = jnp.arange(rows)
        entry, by_token = lax.sort_key_val(
            jnp.where(at < count, index, len(place)), at)
        token = entry // fan
        blocks = tokens // _TOKEN_BLOCK
        which = (token[:, None] % _TOKEN_BLOCK
                 == jnp.arange(_TOKEN_BLOCK)).astype(table.dtype)
        sizes = jnp.sum(token[:, None] // _TOKEN_BLOCK == jnp.arange(blocks),
                        axis=0, dtype=jnp.int32)
        _metrics.inc("kernels.traced.tgmm")
        with jax.named_scope("moe_token_sums"):
            sums = _grouped_matmul_ops.tgmm(which, table[by_token], sizes,
                                            dtype, interpret=interpret)
        return sums.reshape(tokens, d)
    picked = jnp.where(((place >= 0) & (place < count))[:, None],
                       table[jnp.clip(place, 0, rows - 1)],
                       jnp.zeros((), table.dtype))
    return picked.reshape(tokens, fan, d).sum(1, dtype=dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_rows(x, index, place, count, fan):
    """``x[index // fan]``: of the ``fan * len(x)`` copies of ``x``'s
    rows, those ``index`` names, which is a window of a permutation of
    them; ``place`` says for every copy where in ``index`` it is named, or
    a number outside it. Of what comes back for the rows only the first
    ``count`` count (None: all), and the transpose is no scatter: each
    row's copies among them, summed (``_placed_sums``)."""
    return x[index // fan]


def _take_rows_fwd(x, index, place, count, fan):
    # Without a count the transpose reads ``place`` alone.
    return x[index // fan], (place, None if count is None else index, count)


def _take_rows_bwd(fan, residuals, g):
    return _placed_sums(g, *residuals, fan, g.dtype), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sum_rows(table, place, index, count, fan):
    """The transpose of ``_take_rows``, in float32: ``_placed_sums`` of
    ``table``, whose own transpose is the gather ``g[index // fan]``."""
    return _placed_sums(table, place, index, count, fan, jnp.float32)


def _sum_rows_fwd(table, place, index, count, fan):
    # The empty slice carries the table's type to the backward pass.
    return (_placed_sums(table, place, index, count, fan, jnp.float32),
            (index, count, table[:0]))


def _sum_rows_bwd(fan, residuals, g):
    index, count, like = residuals
    g = g.astype(like.dtype)
    if count is None:
        # Every row's copies written out and gathered, as reverse mode
        # writes the sum's transpose, which keeps the layer that holds
        # every expert the program it was before there were windows.
        copies = jnp.broadcast_to(g[:, None], (len(g), fan, g.shape[-1]))
        return copies.reshape(len(index), -1)[index], None, None, None
    return g[index // fan], None, None, None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def _gmm_of_layer(lhs, stack, layer, group_sizes, transpose_rhs, interpret):
    """The Pallas grouped matmul of ``lhs`` with the matrices
    ``stack[layer]``, read where they lie: the kernel takes the whole
    stack as ``L * g`` groups of which only this layer's have rows, and
    its index map, which skips empty groups, finds a tile's matrix at
    ``layer * g`` + its group. A slice of the stack would have to be
    copied out first: a custom call's operand is a buffer of its own."""
    _metrics.inc("kernels.traced.gmm")
    n_layers, groups = stack.shape[:2]
    sizes = lax.dynamic_update_slice(
        jnp.zeros(n_layers * groups, jnp.int32), group_sizes,
        (layer * groups,))
    return _grouped_matmul_ops.gmm(
        lhs, stack.reshape((-1,) + stack.shape[2:]), sizes,
        transpose_rhs=transpose_rhs, interpret=interpret)


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
    out = _gmm_of_layer(lhs, stack, layer, group_sizes, False, interpret)
    return out, (lhs, stack, layer, group_sizes)


def _stacked_gmm_bwd(interpret, residuals, grad):
    lhs, stack, layer, group_sizes = residuals
    grad_lhs = _gmm_of_layer(grad, stack, layer, group_sizes, True, interpret)
    _metrics.inc("kernels.traced.tgmm")
    grad_rhs = _grouped_matmul_ops.tgmm(lhs, grad, group_sizes, stack.dtype,
                                        interpret=interpret)
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


# A window of the sorted assignments is twice the rows the held experts
# get at balance: trinity-mini-t8192's seeded routers send 10.3 to 14.0 % of
# the assignments to an eighth of the experts (PERF.md section 7), so one
# window of a quarter holds them with room to spare, and the step's
# ``windows`` says when training moves that.
_WINDOW_OVER_BALANCE = 2


def _window_rows(assignments: int, e_local: int, n_experts: int) -> int:
    """Positions of the sorted assignments a window holds: the least
    multiple of the grouped matmul's row tile that is ``_WINDOW_OVER_
    BALANCE`` times ``assignments * e_local / n_experts`` or more."""
    tile = _grouped_matmul_ops.ROW_TILE
    return -(-_WINDOW_OVER_BALANCE * e_local * assignments
             // (n_experts * tile)) * tile


def _window(x, gates, held, index, place, sizes, stacks, layer, top_k,
            elsewhere):
    """What the held experts give the tokens ``x`` [N, d] for the
    assignments at consecutive positions of the sort, float32 [N, d]:
    ``index`` the assignments there, ``place`` every assignment's
    position counted from the first of them, ``sizes`` the held groups'
    rows among them, from the first position on. ``elsewhere``: the
    positions after the groups hold assignments that no group covers; a
    grouped matmul may leave anything in their rows, in either pass, and
    the sums over a token's rows leave them out."""
    count = jnp.sum(sizes) if elsewhere else None
    with jax.named_scope("moe_dispatch"):
        rows = _take_rows(x, index, place, count, top_k)

    with jax.named_scope("moe_experts"):
        # The router's weight rides the gated product, in float32, into
        # the third matmul: p (h Wd) = (p h) Wd, and the rows need no
        # weighting on their way back.
        weight = _take_rows(gates[:, None], index, place, count, 1)

        def experts(lhs, name):
            return _grouped_matmul(lhs, held[name], sizes,
                                   stacks[name] if stacks else None, layer)

        hidden = _gated(experts(rows, "wg"), experts(rows, "wu"),
                        weight[:, 0])
        out = experts(hidden, "wd")

    with jax.named_scope("moe_combine"):
        return _sum_rows(out, place, index, count, top_k)  # by token again


@functools.partial(jax.jit, static_argnames=("top_k", "rows"))
def _window_from(start, x, gates, held, order, inverse, group_sizes, stacks,
                 layer, *, top_k, rows):
    """``_window`` over the ``rows`` positions of the sort from ``start``
    (``order`` reaches that far): the groups clipped to them. Under
    ``jax.jit`` for what that does to tracing: every layer's window of the
    same shapes is traced once, and the scopes inside stay the path
    segments they are when reverse mode goes through (a scope opened
    under ``jax.vjp`` itself would read ``transpose(jvp(scope))``)."""
    ends = jnp.clip(jnp.cumsum(group_sizes) - start, 0, rows)
    return _window(x, gates, held, lax.dynamic_slice(order, (start,), (rows,)),
                   inverse - start, jnp.diff(ends, prepend=0), stacks, layer,
                   top_k, True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _windows(x, gates, held, order, inverse, group_sizes, windows, stacks,
             layer, top_k, rows):
    """The sum of ``_window_from`` over the first ``windows`` (data)
    windows of ``rows`` positions, in a ``lax.while_loop``. Reverse mode
    is written out because the trip count is data: it keeps the inputs
    alone, takes each window's ``jax.vjp`` in a second loop and adds up
    what they give ``x``, ``gates`` and ``held``, each in its own type;
    ``stacks``, a constant, gets nothing."""
    return _windows_fwd(x, gates, held, order, inverse, group_sizes, windows,
                        stacks, layer, top_k, rows)[0]


def _over_windows(windows, one):
    """``one(0) + one(1) + ... + one(windows - 1)``, trees added leaf by
    leaf to zeros of their own types."""
    def turn(carry):
        at, total = carry
        return at + 1, jax.tree.map(jnp.add, total, one(at))

    zeros = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                         jax.eval_shape(one, jnp.int32(0)))
    return lax.while_loop(lambda carry: carry[0] < windows, turn,
                          (jnp.int32(0), zeros))[1]


def _windows_fwd(x, gates, held, order, inverse, group_sizes, windows,
                 stacks, layer, top_k, rows):
    y = _over_windows(windows, lambda at: _window_from(
        at * rows, x, gates, held, order, inverse, group_sizes, stacks,
        layer, top_k=top_k, rows=rows))
    return y, (x, gates, held, order, inverse, group_sizes, windows, stacks,
               layer)


def _windows_bwd(top_k, rows, residuals, g):
    x, gates, held, order, inverse, group_sizes, windows, stacks, layer = \
        residuals

    def pull(at):
        return jax.vjp(lambda x, gates, held: _window_from(
            at * rows, x, gates, held, order, inverse, group_sizes, stacks,
            layer, top_k=top_k, rows=rows), x, gates, held)[1](g)

    return _over_windows(windows, pull) + (None,) * 6


_windows.defvjp(_windows_fwd, _windows_bwd)


def _in_best_groups(biased, n_group: int, topk_group: int):
    """``biased`` [..., E] with the experts outside the ``topk_group``
    best of ``n_group`` groups of consecutive experts at -inf; a group's
    score is the sum of its two largest entries."""
    E = biased.shape[-1]
    groups = biased.reshape(biased.shape[:-1] + (n_group, E // n_group))
    score = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)  # [..., n_group]
    best = lax.top_k(score, topk_group)[1]
    kept = jnp.any(best[..., None] == jnp.arange(n_group, dtype=best.dtype),
                   axis=-2)  # [..., n_group]
    return jnp.where(kept[..., None], groups, -jnp.inf).reshape(biased.shape)


def moe_layer(x, params, n_experts: int, first=None, axis_name: str = "dp",
              top_k: int = 1, norm_topk_prob: bool = False,
              seq_axis_name=None, stacks=None, layer=0,
              score_func: str = "softmax", route_scale: float = 1.0,
              logits=None, n_group: int = 1, topk_group: int = 1):
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

    ``logits`` float32 [B, T, E]: the caller's own router's, in place of
    ``x @ params["router"]``, which is then not read.

    ``score_func`` ``"softmax"``: the ``top_k`` largest router
    probabilities ``p`` (of ``p + params["expert_bias"]`` where there is
    one: float32 [E], no gradient, the selection only), and ``w = p`` of
    the picked as they are unless ``norm_topk_prob`` divides them by their
    sum. ``"sigmoid"``: scores
    ``s = sigmoid(logits)``; the ``top_k`` largest of ``s +
    params["expert_bias"]`` [E] (float32, no gradient; absent: zero) are
    picked, and ``w = route_scale * s`` of the picked, with
    ``norm_topk_prob`` over their sum (+ 1e-20) first. The sum is over
    all ``top_k`` picked experts, held or not. With ``n_group`` > 1 the
    selection is group-limited (DeepSeek-V3's, arXiv:2412.19437 section
    2.1.2): the experts in ``n_group`` groups of consecutive ones, a group
    scored by the sum of its two largest ``s + bias``, and the ``top_k``
    taken among the experts of the ``topk_group`` best groups.

    Returns ``(y [B, T, d], stats)``. ``stats["lb"]`` is the load-balance
    term ``E * sum_e f_e P_e`` of each sequence (``f_e`` the share of its
    tokens with ``e`` among their ``top_k``, ``P_e`` its mean router
    probability; ``top_k`` at perfect balance), averaged over the local
    sequences; ``stats["z"]`` the mean squared log-sum-exp of the router
    logits (both zero under ``"sigmoid"``, which has no such terms);
    ``stats["load"]`` [E] the local tokens per expert, held or not;
    ``stats["windows"]`` the windows of the sorted assignments this layer
    took (int32; the module's docstring): 1 where the held experts' rows
    fit one, and where one window holds every assignment.
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
    if n_group > 1 and (score_func != "sigmoid" or E % n_group
                        or not 1 <= topk_group <= n_group
                        or top_k > topk_group * (E // n_group)):
        raise ValueError(
            f"n_group={n_group}, topk_group={topk_group}: groups of a "
            f"sigmoid router's {E} experts, of which the kept must hold "
            f"top_k={top_k}")
    with jax.named_scope("moe_route"):
        # float32 in earnest: at default precision the MXU would round
        # both operands to bf16 and the top-k with them.
        if logits is None:
            logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                                params["router"],
                                precision=lax.Precision.HIGHEST)
        if score_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            biased = scores
            if "expert_bias" in params:
                biased = scores + lax.stop_gradient(params["expert_bias"])
            if n_group > 1:
                biased = _in_best_groups(biased, n_group, topk_group)
            experts = lax.top_k(biased, top_k)[1]  # [B, T, k]
            gates = jnp.take_along_axis(scores, experts, axis=-1)
            if norm_topk_prob:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + 1e-20)
            gates = gates * route_scale
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            if "expert_bias" in params:
                experts = lax.top_k(
                    probs + lax.stop_gradient(params["expert_bias"]),
                    top_k)[1]
                gates = jnp.take_along_axis(probs, experts, axis=-1)
            else:
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

    held = {name: params[name] for name in ("wg", "wu", "wd")}
    window = _window_rows(len(order), e_local, E)
    if window >= len(order):
        # One window holds every assignment: its body as it is, no loop.
        y = _window(rows, gates, held, order, inverse, group_sizes, stacks,
                    layer, top_k, e_local < E)
        stats["windows"] = jnp.ones((), jnp.int32)
    else:
        stats["windows"] = jnp.maximum(
            1, -(-jnp.sum(group_sizes) // window))
        with jax.named_scope("moe_window"):
            y = _windows(rows, gates, held,
                         jnp.pad(order, (0, -len(order) % window)), inverse,
                         group_sizes, stats["windows"], stacks, layer, top_k,
                         window)

    with jax.named_scope("moe_combine"):
        if ep > 1:
            y = lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                 tiled=True)
        return y.astype(x.dtype).reshape(B, T, d), stats
