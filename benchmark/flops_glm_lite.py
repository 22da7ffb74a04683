"""Operations and bytes of the GLM-4.7-Flash (``glm4_moe_lite``) decoder
as one chip of a deployment holds it: its latent attention mixers, the
flash kernels under them at the one head width they are given, the held
share of the experts and the multi-token-prediction module, from shapes
and from the run's own count of the rows that fell on held experts. Part
of the yardstick, as ``flops.py`` is: utilization and roofline shares
divide these by measured time. The kernels' and the grouped matmuls'
counts are ``flops.py``'s and ``flops_afmoe.py``'s own functions."""

from benchmark import flops, flops_afmoe

held_matmul_train_flops = flops_afmoe.held_matmul_train_flops
held_matmul_train_bytes = flops_afmoe.held_matmul_train_bytes


def latent_attention_matmul_params(d, n_heads, q_lora_rank, kv_lora_rank,
                                   qk_nope_head_dim, qk_rope_head_dim,
                                   v_head_dim):
    """The five matrices a token passes in one latent mixer: the query's
    down- and up-projection (d x rq, rq x H (nope + rope)), the key and
    value's (d x (rkv + rope), rkv x H (nope + Dv)) and the output
    projection (H Dv x d). The two norms are no matmuls."""
    qk = qk_nope_head_dim + qk_rope_head_dim
    return (d * q_lora_rank + q_lora_rank * n_heads * qk
            + d * (kv_lora_rank + qk_rope_head_dim)
            + kv_lora_rank * n_heads * (qk_nope_head_dim + v_head_dim)
            + n_heads * v_head_dim * d)


def latent_flash_train_flops(batch, heads, seq_len, head_dim):
    """FLOPs one mixer's causal attention needs forward and backward at
    the one width q, k and v enter the kernels with: 7 B H T^2 D
    (``flops.causal_attention_train_flops``)."""
    return flops.causal_attention_train_flops(batch, heads, seq_len,
                                              head_dim)


def latent_flash_train_bytes(batch, heads, seq_len, head_dim, itemsize):
    """Twelve [B, H, T, D] arrays
    (``flops.causal_attention_train_bytes``): K is counted at the H
    heads it is assembled to before the kernels, its shared rotated part
    H times over, because that is what the kernels are given."""
    return flops.causal_attention_train_bytes(batch, heads, seq_len,
                                              head_dim, itemsize)


def glm_lite_train_flops_per_token(d, n_heads, q_lora_rank, kv_lora_rank,
                                   qk_nope_head_dim, qk_rope_head_dim,
                                   v_head_dim, d_ff, d_expert, n_experts,
                                   n_shared_experts, n_layers,
                                   num_dense_layers, n_mtp_modules,
                                   vocab_rows, seq_len,
                                   held_rows_per_token):
    """Forward + backward model FLOPs per token of what this chip holds:
    6 per matmul parameter a token passes (2 forward, 4 backward) plus
    causal attention, ``12 H Dh T / 2`` a mixer (Dh = nope + rope, the
    value's width too). A leading dense layer passes the gated MLP (3 d
    F); an expert layer the router (d E), the shared experts (3 d f
    each) and the held experts its tokens were routed to,
    ``held_rows_per_token`` of them (3 d f each; the mean over the expert
    layers of the run's own count, 0.5 at balance with an eighth of the
    experts held and 4 a token). A multi-token-prediction module is its
    projection (2 d x d), one more expert layer with its mixer, and the
    head's slice once more (d x rows). The embeddings are gathers.
    Recomputation is not counted."""
    mixer = 6 * latent_attention_matmul_params(
        d, n_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
        qk_rope_head_dim, v_head_dim) + 12 * n_heads * (
        qk_nope_head_dim + qk_rope_head_dim) * seq_len / 2
    expert = 3 * d * d_expert
    sparse = 6 * (d * n_experts + n_shared_experts * expert
                  + held_rows_per_token * expert)
    head = 6 * d * vocab_rows
    return ((n_layers + n_mtp_modules) * mixer
            + num_dense_layers * 6 * 3 * d * d_ff
            + (n_layers - num_dense_layers + n_mtp_modules) * sparse
            + (1 + n_mtp_modules) * head + n_mtp_modules * 6 * 2 * d * d)
