"""Operations and bytes of the Granite 4.0-H hybrid decoder and of its
state-space-duality scan, from shapes. Part of the yardstick, as
``flops.py`` is: utilization and roofline shares divide these by measured
time.

Convention, per token, forward + backward: 6 per matmul parameter (2
forward, 4 backward; the tied table once, as the head: the embedding is a
gather) + 6 T d a causal attention layer (``flops.py``'s: QK^T and PV
forward and backward, halved by the mask) + 3 x (2 Q N G + 2 Q P H + 4 N P
H) a Mamba-2 layer for its scan in the chunked form at the published
chunk Q: the ``C B^T`` scores of a chunk (2 Q N a token and group), the
masked scores against ``dt x`` (2 Q P a token and head), a chunk's state
and the carried state read through ``C`` (2 N P each a token and head);
three times over for the forward and the two gradients of every matmul.
The decays' ``exp``, the convolution, the norms and the gates are not
matmuls and are not counted; neither is recomputation."""


def mamba_mixer_matmul_params(d, heads, d_head, d_state, groups=1):
    """The in-projection (z, x, B, C, dt) and the out-projection."""
    inner = heads * d_head
    return d * (2 * inner + 2 * groups * d_state + heads) + inner * d


def attention_mixer_matmul_params(d, n_heads, n_kv_heads, head_dim):
    """Q, K, V over grouped key/value heads, and the output."""
    return d * head_dim * (n_heads + 2 * n_kv_heads) + n_heads * head_dim * d


def hybrid_matmul_params(d, d_ff, layer_types, n_heads, n_kv_heads,
                         head_dim, mamba_heads, mamba_d_head,
                         mamba_d_state, vocab_rows):
    """Parameters that are matrix-multiplied per token: each layer's mixer
    and its gated MLP (3 d d_ff), plus the tied table once."""
    mixer = {
        "attention": attention_mixer_matmul_params(d, n_heads, n_kv_heads,
                                                   head_dim),
        "mamba": mamba_mixer_matmul_params(d, mamba_heads, mamba_d_head,
                                           mamba_d_state)}
    return (sum(mixer[kind] + 3 * d * d_ff for kind in layer_types)
            + d * vocab_rows)


def ssd_train_flops_per_token(chunk, d_state, groups, d_head, heads):
    """One Mamba-2 layer's chunked scan, forward and backward, a token."""
    return 3 * (2 * chunk * d_state * groups + 2 * chunk * d_head * heads
                + 4 * d_state * d_head * heads)


def hybrid_train_flops_per_token(d, d_ff, layer_types, n_heads, n_kv_heads,
                                 head_dim, mamba_heads, mamba_d_head,
                                 mamba_d_state, mamba_chunk, vocab_rows,
                                 seq_len):
    """Forward + backward model FLOPs per token by the convention above."""
    kinds = list(layer_types)
    return (6 * hybrid_matmul_params(d, d_ff, kinds, n_heads, n_kv_heads,
                                     head_dim, mamba_heads, mamba_d_head,
                                     mamba_d_state, vocab_rows)
            + kinds.count("attention") * 6 * seq_len * n_heads * head_dim
            + kinds.count("mamba") * ssd_train_flops_per_token(
                mamba_chunk, mamba_d_state, 1, mamba_d_head, mamba_heads))


def ssd_train_flops(tokens, chunk, d_state, groups, d_head, heads):
    """FLOPs one layer's scan needs forward and backward over ``tokens``."""
    return tokens * ssd_train_flops_per_token(chunk, d_state, groups,
                                              d_head, heads)


def ssd_train_bytes(tokens, d_state, groups, d_head, heads, itemsize):
    """Least bytes one layer's scan moves to and from HBM, forward and
    backward, if every array crosses once a direction and nothing else
    does: ``x`` read and ``y`` written forward, ``dy`` read and ``dx``
    written backward, four [T, H P] arrays in the model's type; ``B`` and
    ``C`` [T, G N] read and their gradients written; ``dt`` [T, H]
    float32 read and its gradient written. The decay and score arrays,
    the chunk states and a second read of ``x`` backward are left out: a
    fused kernel need not move them. A lower bound, so a share of it
    cannot pass 100 %."""
    wide = 4 * tokens * heads * d_head * itemsize
    narrow = 2 * 2 * tokens * groups * d_state * itemsize
    steps = 2 * tokens * heads * 4
    return wide + narrow + steps
