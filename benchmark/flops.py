"""Operations the algorithms need, from their shapes. Part of the
yardstick: utilization and roofline shares divide these by measured
time, so a PR that changes the program cannot change them."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind):
    """Published peaks of one chip of ``device_kind`` (peaks.json). A
    device that is not in the table is an error, never a default: a
    share of a guessed peak is not a measurement."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    kind = (device_kind or "").lower()
    for row in table["by_device_kind"]:
        if row["match"] in kind:
            return row
    raise ValueError(
        f"no peaks on record for device_kind {device_kind!r}; add a row "
        f"to benchmark/peaks.json with its source")


def decoder_matmul_params(n_embd, n_layer, d_ff, vocab_rows):
    """Parameters that are matrix-multiplied per token in a GPT-2-style
    decoder with an untied head: per layer QKV (3 d^2), the attention
    output (d^2) and the two MLP matrices (2 d d_ff), plus the head
    (d x rows). Embedding and position tables are gathers, not matmuls."""
    per_layer = 4 * n_embd * n_embd + 2 * n_embd * d_ff
    return n_layer * per_layer + n_embd * vocab_rows


def decoder_train_flops_per_token(n_embd, n_layer, d_ff, vocab_rows,
                                  seq_len):
    """Forward + backward model FLOPs per token: 6 per matmul parameter
    (2 forward, 4 backward) plus causal attention, 6 L T d — QK^T and PV
    are 2 x 2 T d forward per layer, three times that with the backward,
    and half of it under a causal mask. Recomputation is not counted."""
    dense = 6 * decoder_matmul_params(n_embd, n_layer, d_ff, vocab_rows)
    attention = 6 * n_layer * seq_len * n_embd
    return dense + attention


def causal_attention_train_flops(batch, heads, seq_len, head_dim):
    """FLOPs one layer's causal attention needs forward and backward:
    7 B H T^2 D. Forward is two T x T x D matmuls (QK^T, PV) and the
    backward five (S again, dP, dV, dQ, dK), 2 B H T^2 D each over the
    full square, half of it under the causal mask."""
    return 7 * batch * heads * seq_len * seq_len * head_dim


def causal_attention_train_bytes(batch, heads, seq_len, head_dim, itemsize):
    """Bytes one layer's attention has to move to and from HBM, forward
    and backward, if nothing is read twice: the forward reads Q, K, V and
    writes O; the backward reads Q, K, V, O, dO and writes dQ, dK, dV —
    twelve [B, H, T, D] arrays. The per-row softmax statistics (B H T
    float32 values) are left out: 1/32 of one array at D 64."""
    return 12 * batch * heads * seq_len * head_dim * itemsize
