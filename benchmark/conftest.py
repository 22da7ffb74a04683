"""The tiny sizes of the cells added after ``tests/tiny.py`` was written,
registered with it before any test builds its tiny copy: a cell's
rehearsal on the CPU runs a few hundred tokens, never a published
width."""
from benchmark.tests import tiny

tiny.TINY_CONFIGS.setdefault("olmoe-1b-7b", dict(
    hidden_size=64, intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=4, num_experts=8, num_experts_per_tok=3,
    num_hidden_layers=2, max_position_embeddings=64, vocab_size=256,
    dtype="float32"))
tiny.TINY_TRAFFIC.setdefault("t4096-b2", dict(batch_per_chip=2, seq_len=32))
