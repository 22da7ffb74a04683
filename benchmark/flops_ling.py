"""Operations and bytes of the Ling-3.0-flash (``bailing_hybrid``) decoder
as one chip of a deployment holds it: its KDA mixers and the delta-rule scan
under them, its latent mixers and the flash kernels at the two widths they
are given, the held share of the experts and the multi-token-prediction
module, from shapes and from the run's own count of the rows that fell on
held experts. Part of the yardstick, as ``flops.py`` is: utilization and
roofline shares divide these by measured time. The grouped matmuls' counts
are ``flops_afmoe.py``'s own functions.

The scan is counted as the recurrence, whatever implements it: a token and
head decays the state (no matmul) and makes three products with it, ``S^T
k`` (the prediction), ``k u^T`` (the write) and ``S^T q`` (the read), 2 K V
each; three times over for the forward and the two gradients of every
product. The chunked form's tiles and its solve are how a kernel makes
those products, not model FLOPs: they show as a lower share."""

from benchmark import flops_afmoe

held_matmul_train_flops = flops_afmoe.held_matmul_train_flops
held_matmul_train_bytes = flops_afmoe.held_matmul_train_bytes


def kda_matmul_params(d, n_heads, head_dim):
    """The six matrices a token passes in one KDA mixer: the query's, the
    key's and the value's projection, the decay's and the output gate's
    (d x H K each), beta's (d x H) and the output projection (H K x d).
    The convolutions, the norms and the gates are no matmuls."""
    return 6 * d * n_heads * head_dim + d * n_heads


def kda_scan_train_flops(batch, heads, seq_len, k_dim, v_dim):
    """FLOPs one mixer's scan needs forward and backward: three products
    of 2 K V a token and head, three times over (the module's docstring):
    18 B H T K V."""
    return 18 * batch * heads * seq_len * k_dim * v_dim


def kda_scan_train_bytes(batch, heads, seq_len, k_dim, v_dim, itemsize):
    """Least bytes one mixer's scan moves to and from HBM, forward and
    backward, if every array crosses once a direction and nothing else
    does. Forward: q, k [K] and v [V] read, the log decay [K] float32 and
    beta float32 read, o [V] written. Backward: all of those read again
    with do [V], and dq, dk [K], dv [V], the decay's gradient [K] float32
    and beta's written. The states a chunk starts from, which the kernels
    keep for their backward pass, are left out: a lower bound, so a share
    of it cannot pass 100 %."""
    rows = batch * heads * seq_len
    forward = rows * (2 * k_dim * itemsize + 2 * v_dim * itemsize
                      + 4 * k_dim + 4)
    backward = rows * (4 * k_dim * itemsize + 3 * v_dim * itemsize
                       + 8 * k_dim + 8)
    return forward + backward


def latent_attention_matmul_params(d, n_heads, kv_lora_rank,
                                   qk_nope_head_dim, qk_rope_head_dim,
                                   v_head_dim):
    """The five matrices a token passes in one latent mixer with no query
    rank: the query's (d x H (nope + rope)), the key and value's down- and
    up-projection (d x (rkv + rope), rkv x H (nope + Dv)), the gate a head
    (d x H) and the output projection (H Dv x d)."""
    return (d * n_heads * (qk_nope_head_dim + qk_rope_head_dim)
            + d * (kv_lora_rank + qk_rope_head_dim)
            + kv_lora_rank * n_heads * (qk_nope_head_dim + v_head_dim)
            + d * n_heads + n_heads * v_head_dim * d)


def latent_flash_train_flops(batch, heads, seq_len, qk_dim, v_dim):
    """FLOPs one mixer's causal attention needs forward and backward at
    the widths the mathematics has: over the ``T^2 / 2`` pairs of the
    causal half, four products over a key's width (QK^T forward; S again,
    dQ and dK backward) and three over a value's (PV; dP, dV), 2 FLOPs a
    multiply-add. The values' zeros up to the keys' width, which the
    kernels multiply, are no work: they show as a lower share."""
    return (batch * heads * seq_len * seq_len / 2
            * 2 * (4 * qk_dim + 3 * v_dim))


def latent_flash_train_bytes(batch, heads, seq_len, qk_dim, v_dim,
                             itemsize):
    """``flops.causal_attention_train_bytes``'s twelve arrays at their own
    widths: q and k three times each (read forward, read backward, their
    gradient written), v three times, o twice and do once."""
    return (batch * heads * seq_len * itemsize
            * (6 * qk_dim + 6 * v_dim))


def ling_train_flops_per_token(d, n_heads, head_dim, kv_lora_rank,
                               qk_nope_head_dim, qk_rope_head_dim,
                               v_head_dim, d_ff, d_expert, n_experts,
                               n_shared_experts, layer_types,
                               mtp_layer_type, num_dense_layers,
                               n_mtp_modules, vocab_rows, seq_len,
                               held_rows_per_token):
    """Forward + backward model FLOPs per token of what this chip holds: 6
    per matmul parameter a token passes (2 forward, 4 backward); a KDA
    mixer's scan, 18 H K V; a latent mixer's causal attention, 2 H (4 Dqk
    + 3 Dv) T / 2. A leading dense layer passes the gated MLP (3 d F); an
    expert layer the router (d E), the shared experts (3 d f each) and the
    held experts its tokens were routed to, ``held_rows_per_token`` of
    them (3 d f each; the mean over the expert layers of the run's own
    count, 0.125 at balance with 8 of 512 experts held and 8 a token). A
    multi-token-prediction module is its projection (2 d x d), one more
    expert layer with its mixer, and the head's slice once more (d x
    rows). The embeddings are gathers. Recomputation is not counted."""
    qk = qk_nope_head_dim + qk_rope_head_dim
    mixer = {
        "kda": 6 * kda_matmul_params(d, n_heads, head_dim)
        + 18 * n_heads * head_dim * head_dim,
        "latent_attention": 6 * latent_attention_matmul_params(
            d, n_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim)
        + 2 * n_heads * (4 * qk + 3 * v_head_dim) * seq_len / 2}
    kinds = list(layer_types) + [mtp_layer_type] * n_mtp_modules
    expert = 3 * d * d_expert
    sparse = 6 * (d * n_experts + n_shared_experts * expert
                  + held_rows_per_token * expert)
    head = 6 * d * vocab_rows
    return (sum(mixer[kind] for kind in kinds)
            + num_dense_layers * 6 * 3 * d * d_ff
            + (len(layer_types) - num_dense_layers + n_mtp_modules) * sparse
            + (1 + n_mtp_modules) * head + n_mtp_modules * 6 * 2 * d * d)
