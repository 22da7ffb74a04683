"""A copy of the benchmark cut to sizes a CPU runs in seconds: the same
files, BENCHMARK.json included, with the widths and batches shrunk. What
the tests rehearse is the harness, never a number."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIGS = {
    # float32 in both: at these sizes bf16's roundings do not average out
    # (a few hundred tokens; batch 8 with one pixel a channel in the last
    # stage), and what a rehearsal checks is the references' logic.
    "resnet50": dict(stage_sizes=[1, 1, 1, 1], num_filters=8,
                     num_classes=10, image_size=32, dtype="float32"),
    "gpt2s": dict(n_embd=64, n_head=4, n_layer=2, n_positions=64,
                  vocab_size=250, dtype="float32"),
}
TINY_ASSUMED = {"gpt2s": dict(head_dim=16, d_ff=256, padded_vocab_rows=256)}
TINY_TRAFFIC = {
    "1chip-b256": dict(batch_per_chip=8),
    "dp4-b128": dict(batch_per_chip=4),
    "t1024-b8": dict(batch_per_chip=2, seq_len=64),
    "t128-b64": dict(batch_per_chip=4, seq_len=16),
}


def _rewrite(path, change):
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make_tiny_copy(dest):
    """BENCHMARK.json and benchmark/ copied under ``dest`` and shrunk."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    bench = os.path.join(dest, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, sizes in TINY_CONFIGS.items():
        def shrink(cfg, name=name, sizes=sizes):
            cfg.update(sizes)
            cfg["assumed"].update(TINY_ASSUMED.get(name, {}))
        _rewrite(os.path.join(bench, "configs", name + ".json"), shrink)
    for name, sizes in TINY_TRAFFIC.items():
        _rewrite(os.path.join(bench, "workloads", name + ".json"),
                 lambda t, sizes=sizes: t.update(
                     sizes, steps_per_chunk=2, chunks_queued=2, trace_steps=3,
                     warmup_steps=2))
    return dest
