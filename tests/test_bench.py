"""bench.py and tools/transformer_bench.py need the chip; their command
lines refuse the CPU (tests/test_chip_smoke.py). What can rot without a
chip is the code under them, so these drive the same functions end to
end at toy sizes — on the CPU backend on purpose, by importing them in a
child process, and every result says so: ``"platform": "cpu"``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# The functions have one path, which divides by the device's peak; the
# CPU is in no peak table (an unknown device raises), so the toy run
# brings a made-up entry of its own. Nothing reads the share it gives.
_PRELUDE = ("import json\nimport bench\n"
            "bench.PEAK_FLOPS_BY_KIND = [('cpu', 1e12)]\n")


def _toy_run(snippet):
    from conftest import subprocess_cpu_env

    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE + snippet],
        capture_output=True, text=True, timeout=420,
        env=subprocess_cpu_env(), cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")][-1]
    parsed = json.loads(line)
    assert parsed["platform"] == "cpu"
    assert parsed["device_count"] == 1
    return parsed


@pytest.mark.full
def test_resnet_bench_toy_run():
    parsed = _toy_run(
        "args = bench._build_parser().parse_args(['--batch-size', '2', "
        "'--num-warmup', '0', '--num-iters', '1', '--image-size', '64'])\n"
        "print(json.dumps(bench.resnet_bench(args)))\n")
    assert parsed["metric"] == "resnet50_images_per_sec_per_chip"
    assert parsed["value"] > 0
    assert parsed["unit"] == "images/sec/chip"
    assert parsed["workload"]["batch_size"] == 2
    assert "vs_baseline" in parsed


@pytest.mark.full
def test_transformer_bench_toy_run():
    parsed = _toy_run(
        "import tools.transformer_bench as tb\n"
        "args = tb._build_parser().parse_args(['--d-model', '64', "
        "'--n-heads', '4', '--n-layers', '2', '--vocab', '256', "
        "'--seq-len', '64', '--batch-size', '4', '--num-warmup', '1', "
        "'--num-iters', '2'])\n"
        "print(json.dumps(tb.run(args)))\n")
    assert parsed["metric"] == "transformer_tokens_per_sec_per_chip"
    assert parsed["value"] > 0
    assert parsed["n_params"] > 0
    assert parsed["loss"] > 0
