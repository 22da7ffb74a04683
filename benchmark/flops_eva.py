"""Operations and bytes of the EvaByte (``evabyte``) decoder: its EVA
mixers, the flash kernels under them over both key sets, the gated MLP and
the eight prediction heads, from shapes. Part of the yardstick, as
``flops.py`` is: utilization and roofline shares divide these by measured
time."""


def eva_pairs(seq_len, window, chunk):
    """(exact, summary) query-key pairs a head's softmax holds over one
    sequence: a window's own keys up to the query, ``W (W + 1) / 2`` a
    window; ``W / C`` summaries of every earlier window for each of a
    window's ``W`` queries. 33.6 M and 31.5 M at T 32,768, W 2,048, C 16
    (the diagonal's 32,768 pairs counted)."""
    window = min(window, seq_len)
    n = seq_len // window
    exact = n * window * (window + 1) // 2
    summary = window * (window // chunk) * n * (n - 1) // 2
    return exact, summary


def eva_matmul_params(d, n_heads, head_dim, d_ff):
    """The multiply-adds a token passes in one layer, as parameters: the
    three projections and the output projection (4 d H D) and the gated
    MLP's three matrices (3 d F). The poolings are no matmuls (a product
    and a sum over a chunk's 16 tokens, 4 D multiply-adds a token and
    head)."""
    return 4 * d * n_heads * head_dim + 3 * d * d_ff


def eva_flash_train_flops(batch, heads, head_dim, seq_len, window, chunk):
    """FLOPs one mixer's kernels need forward and backward: seven matmuls
    over every visible pair of either set (forward S and P V; backward S
    again, dP, dV, dQ, dK), 2 D each: ``14 B H D pairs``, which is
    ``flops.causal_attention_train_flops``'s ``7 B H T^2 D`` where the
    pairs are ``T^2 / 2``."""
    return 14 * batch * heads * head_dim * sum(eva_pairs(seq_len, window,
                                                         chunk))


def eva_flash_train_bytes(batch, heads, head_dim, seq_len, chunk, itemsize):
    """Bytes one mixer's kernels have to move if nothing is read twice:
    ``flops.causal_attention_train_bytes``'s twelve [B, H, T, D] arrays
    (Q, K, V read and O written forward; Q, K, V, O, dO read and dQ, dK,
    dV written backward) and six [B, H, T / C, D] (the summaries read in
    either pass, their gradients written)."""
    return (12 * seq_len + 6 * (seq_len // chunk)) * batch * heads \
        * head_dim * itemsize


def eva_train_flops_per_token(d, n_heads, head_dim, d_ff, n_layers,
                              vocab_rows, n_pred_heads, seq_len, window,
                              chunk):
    """Forward + backward model FLOPs per token: 6 per matmul parameter a
    token passes (2 forward, 4 backward) plus the mixers' attention at 12
    H D a visible pair (six matmuls: the backward's second S is
    recomputation), the pairs a token's mean over the sequence; the head
    is ``n_pred_heads`` matrices of ``d x vocab_rows``. The table's gather
    is a gather. Recomputation is not counted."""
    pairs = sum(eva_pairs(seq_len, window, chunk)) / seq_len
    layer = 6 * eva_matmul_params(d, n_heads, head_dim, d_ff) \
        + 12 * n_heads * head_dim * pairs
    return n_layers * layer + 6 * d * n_pred_heads * vocab_rows
