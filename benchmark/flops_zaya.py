"""Operations and bytes of the ZAYA1-8B (``zaya``) decoder as one chip of
a deployment holds it: its CCA mixers, the flash kernels under them at
their own head counts, the router's MLP, the held share of the experts and
the tied head's slice, from shapes and from the run's own count of the
rows that fell on held experts. Part of the yardstick, as ``flops.py`` is:
utilization and roofline shares divide these by measured time. The
kernels' FLOPs and the grouped matmuls' counts are ``flops.py``'s and
``flops_afmoe.py``'s own functions."""

from benchmark import flops, flops_afmoe

held_matmul_train_flops = flops_afmoe.held_matmul_train_flops
held_matmul_train_bytes = flops_afmoe.held_matmul_train_bytes


def cca_matmul_params(d, n_heads, n_kv_heads, head_dim, time0, time1):
    """The multiply-adds a token passes in one CCA mixer, as parameters:
    the three projections into the compressed space (d x Hq Dh, twice d x
    Hkv Dh), the output projection (Hq Dh x d), the convolution by head
    (time1 taps of [Dh, Dh] a head, queries and keys) and the depthwise
    one (time0 taps a channel). The q-k mean, the norm and the rotation
    are no matmuls."""
    heads = n_heads + n_kv_heads
    return (d * head_dim * (2 * n_heads + 2 * n_kv_heads)
            + time1 * heads * head_dim * head_dim
            + time0 * heads * head_dim)


def router_matmul_params(d, router_hidden, n_experts):
    """The ZAYA router's four matrices: d x R, twice R x R, R x E."""
    return (d * router_hidden + 2 * router_hidden * router_hidden
            + router_hidden * n_experts)


def cca_flash_train_flops(batch, heads, seq_len, head_dim):
    """FLOPs one mixer's causal attention needs forward and backward at
    the query heads' count: 7 B Hq T^2 D
    (``flops.causal_attention_train_flops``); a key/value head is read by
    its group and costs no FLOPs of its own."""
    return flops.causal_attention_train_flops(batch, heads, seq_len,
                                              head_dim)


def cca_flash_train_bytes(batch, heads, kv_heads, seq_len, head_dim,
                          itemsize):
    """``flops.causal_attention_train_bytes``'s twelve arrays with the six
    of the K side (K, V read forward and backward, dK, dV written) at the
    Hkv heads the kernels are given: six [B, Hq, T, D] and six [B, Hkv, T,
    D]."""
    return 6 * batch * (heads + kv_heads) * seq_len * head_dim * itemsize


def head_train_flops(tokens, d, vocab_rows):
    """FLOPs the head's slice needs forward and backward: 6 d rows a token
    (the logits, and the gradient by the hidden states and by the table).
    The block-wise head forms every block's logits a second time in the
    backward pass: recomputation, in the time and not in the FLOPs."""
    return 6 * tokens * d * vocab_rows


def zaya_train_flops_per_token(d, n_heads, n_kv_heads, head_dim, time0,
                               time1, router_hidden, n_experts, d_expert,
                               n_layers, vocab_rows, seq_len,
                               held_rows_per_token):
    """Forward + backward model FLOPs per token of what this chip holds:
    6 per matmul parameter a token passes (2 forward, 4 backward; the
    convolutions counted as the matmuls they are) plus causal attention,
    ``12 Hq Dh T / 2`` a mixer. An expert layer passes the router's MLP
    and the held expert its token was routed to, ``held_rows_per_token``
    of them (3 d f each; the mean over the layers of the run's own count,
    0.5 at balance with half of the experts held and one a token). The
    head's slice is 6 d rows; the tied table's gather at the other end is
    a gather. Recomputation is not counted."""
    mixer = 6 * cca_matmul_params(d, n_heads, n_kv_heads, head_dim, time0,
                                  time1) + 12 * n_heads * head_dim \
        * seq_len / 2
    sparse = 6 * (router_matmul_params(d, router_hidden, n_experts)
                  + held_rows_per_token * 3 * d * d_expert)
    return n_layers * (mixer + sparse) + 6 * d * vocab_rows
