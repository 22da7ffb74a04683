"""Operations and bytes of the Trinity (``afmoe``) decoder as one chip of
a deployment holds it, of its windowed attention kernels and of the
grouped matmuls over the experts held, from shapes and from the run's own
count of the rows that fell on held experts. Part of the yardstick, as
``flops.py`` is: utilization and roofline shares divide these by measured
time."""


def attended_pairs(seq_len, window=None):
    """Query-key pairs a sequence's causal attention holds: ``T^2 / 2``
    without a window (``flops.py``'s convention for the causal half), and
    with one the pairs ``j <= i``, ``i - j < W``: ``W (W + 1) / 2`` in the
    first ``W`` rows and ``W`` in each of the ``T - W`` after them."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2
    return window * (window + 1) / 2 + (seq_len - window) * window


def attention_train_flops(batch, heads, seq_len, head_dim, window=None):
    """FLOPs one layer's attention needs forward and backward: seven
    matmuls over the attended pairs (QK^T and PV forward; S again, dP,
    dV, dQ, dK backward) at 2 D FLOPs a pair and head: 14 B H D pairs.
    ``flops.causal_attention_train_flops`` with the pairs in place of
    ``T^2 / 2``."""
    return 14 * batch * heads * head_dim * attended_pairs(seq_len, window)


def attention_train_bytes(batch, heads, seq_len, head_dim, itemsize):
    """``flops.causal_attention_train_bytes``: twelve [B, H, T, D] arrays,
    whatever the window, with K and V at the H heads the kernels are
    given."""
    return 12 * batch * heads * seq_len * head_dim * itemsize


def held_matmul_train_flops(rows_held, d, d_expert):
    """FLOPs one layer's three grouped matmuls over the held experts
    need forward and backward: the rows that fell on a held expert
    through three d x f matrices at 2 FLOPs a multiply-add, three times
    over (the forward, and for each matmul the gradient by its rows and
    by its weights): 18 rows d f. The rows of experts held elsewhere are
    no work of this chip's."""
    return 18 * rows_held * d * d_expert


def held_matmul_train_bytes(rows_held, d, d_expert, experts_held, itemsize):
    """Least bytes those matmuls move (``flops_moe``'s count): the held
    experts' three matrices four times (read forward, twice backward,
    their gradients written), five [rows, d] arrays of the held rows."""
    weights = 3 * experts_held * d * d_expert * itemsize
    return 4 * weights + 5 * rows_held * d * itemsize


def attention_matmul_params(d, n_heads, n_kv_heads, head_dim):
    """Q, the gate and the output projection (d x H Dh each) and K, V
    (d x Hkv Dh each)."""
    return 3 * d * n_heads * head_dim + 2 * d * n_kv_heads * head_dim


def afmoe_train_flops_per_token(d, n_heads, n_kv_heads, head_dim, d_ff,
                                d_expert, n_experts, n_shared_experts,
                                layer_types, num_dense_layers,
                                sliding_window, vocab_rows, seq_len,
                                held_rows_per_token):
    """Forward + backward model FLOPs per token of what this chip holds:
    6 per matmul parameter a token passes (2 forward, 4 backward) plus
    attention over the attended pairs, ``12 H Dh pairs / T`` a layer (H Dh
    is not d here). A leading dense layer passes the gated MLP (3 d F); an
    expert layer the router (d E), the shared experts (3 d f each) and
    the held experts its tokens were routed to, ``held_rows_per_token``
    of them (3 d f each; the mean over the expert layers of the run's
    own count, 1 at balance with an eighth of the experts held and 8 a
    token); the head its slice (d x rows). The embedding is a gather.
    Recomputation is not counted."""
    attention = attention_matmul_params(d, n_heads, n_kv_heads, head_dim)
    expert = 3 * d * d_expert
    total = 6 * d * vocab_rows
    for at, kind in enumerate(layer_types):
        window = sliding_window if kind == "sliding_attention" else None
        total += 6 * attention + 12 * n_heads * head_dim * attended_pairs(
            seq_len, window) / seq_len
        if at < num_dense_layers:
            total += 6 * 3 * d * d_ff
        else:
            total += 6 * (d * n_experts + n_shared_experts * expert
                          + held_rows_per_token * expert)
    return total
