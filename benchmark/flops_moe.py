"""Operations and bytes of the OLMoE-style decoder and of its grouped
expert matmuls, from shapes. Part of the yardstick, as ``flops.py`` is:
utilization and roofline shares divide these by measured time."""


def decoder_moe_matmul_params(d, layers, d_expert, experts_per_token,
                              n_experts, vocab_rows):
    """Parameters that are matrix-multiplied per token, an expert counted
    only for the tokens that reach it: per layer Q, K, V and the
    attention output (4 d^2), the router (d E), and ``experts_per_token``
    experts of three matrices (3 d f) each; plus the untied head (d x
    rows). The embedding is a gather, not a matmul."""
    per_layer = (4 * d * d + d * n_experts
                 + experts_per_token * 3 * d * d_expert)
    return layers * per_layer + d * vocab_rows


def decoder_moe_train_flops_per_token(d, layers, d_expert,
                                      experts_per_token, n_experts,
                                      vocab_rows, seq_len):
    """Forward + backward model FLOPs per token: 6 per active matmul
    parameter (2 forward, 4 backward) plus causal attention, 6 L T d
    (``flops.decoder_train_flops_per_token``'s convention). Recomputation
    is not counted."""
    return (6 * decoder_moe_matmul_params(d, layers, d_expert,
                                          experts_per_token, n_experts,
                                          vocab_rows)
            + 6 * layers * seq_len * d)


def grouped_matmul_train_flops(tokens, experts_per_token, d, d_expert):
    """FLOPs one layer's three grouped matmuls need forward and
    backward: ``k T`` rows through three d x f matrices at 2 FLOPs a
    multiply-add, three times over (the forward, and for each matmul the
    gradient by its rows and by its weights): 18 k T d f. It does not
    depend on how the rows fall among the experts."""
    return 18 * experts_per_token * tokens * d * d_expert


def grouped_matmul_train_bytes(tokens, experts_per_token, d, d_expert,
                               n_experts, itemsize):
    """Least bytes one layer's three grouped matmuls move to and from
    HBM, forward and backward, if no pass reads anything twice. Every
    expert's three matrices are read forward and twice backward (for the
    gradient by the rows, and again where the gated product is formed
    anew for the weight gradients' operands) and their gradients written
    once: four times the weights. The gathered rows [k T, d] are read
    forward and again backward (the weight gradients' operand), the
    result [k T, d] is written forward, its gradient read and the rows'
    gradient written backward: five such arrays. The hidden [k T, f]
    arrays between the matmuls are left out: a fused kernel need not
    write them. Every expert is counted whether or not a token reached
    it: at 8 of 64 over thousands of tokens all do."""
    weights = 3 * n_experts * d * d_expert * itemsize
    rows = experts_per_token * tokens * d * itemsize
    return 4 * weights + 5 * rows
