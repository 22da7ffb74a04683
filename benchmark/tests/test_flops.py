"""The FLOP and byte functions against counts made by hand."""
import json
import os

import pytest

from benchmark import flops

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_gpt2s_flops_per_token_by_hand():
    c = _config("gpt2s")
    dims = dict(n_embd=c["n_embd"], n_layer=c["n_layer"],
                d_ff=c["assumed"]["d_ff"],
                vocab_rows=c["assumed"]["padded_vocab_rows"])
    # Per layer: QKV 3 x 768^2, output 768^2, MLP 2 x 768 x 3072
    # = 7,077,888; twelve layers 84,934,656; head 768 x 50,304
    # = 38,633,472; together 123,568,128 matmul parameters.
    assert flops.decoder_matmul_params(**dims) == 123_568_128
    # 6 x that = 741.4 M; attention 6 x 12 x 1024 x 768 = 56.6 M.
    assert flops.decoder_train_flops_per_token(seq_len=1024, **dims) == \
        741_408_768 + 56_623_104
    assert flops.decoder_train_flops_per_token(seq_len=128, **dims) == \
        741_408_768 + 7_077_888


def test_resnet50_flops_per_image_by_hand():
    # 4.1 GMAC forward, x 2 FLOPs per MAC, x 3 for forward + backward.
    assert _config("resnet50")["flops"]["train_flops_per_image"] == \
        pytest.approx(3 * 2 * 4.1e9)


def test_causal_attention_counts_by_hand():
    # B 8, H 12, T 1024, D 64: one full T x T x D matmul is
    # 2 x 8 x 12 x 1024^2 x 64 = 12,884,901,888 FLOPs; two forward and
    # five backward, halved by the mask: 3.5 of them.
    assert flops.causal_attention_train_flops(8, 12, 1024, 64) == \
        3.5 * 12_884_901_888
    # Twelve [8, 12, 1024, 64] bf16 arrays of 12,582,912 bytes each.
    assert flops.causal_attention_train_bytes(8, 12, 1024, 64, 2) == \
        12 * 12_582_912


def test_peaks_are_looked_up_by_kind_and_never_guessed():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        flops.peaks_for("cpu")
