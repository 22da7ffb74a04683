"""The harness rehearsed on the CPU at tiny sizes: every cell of
BENCHMARK.json end to end (``resnet50-dp4`` on four virtual devices),
the refusal to measure without a chip, and the proof that a
configuration, a traffic mix, a cell and a per-layer metric are added as
files and entries with no edit to a file that is there."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness
from benchmark.tests import tiny

ROOT = tiny.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_tiny_copy(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_untraced(tiny_root, workload):
    result = harness.run_cell(workload, seed=3, seconds=0.2, trace=False,
                              t_start=time.perf_counter(), root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {
        "samples_per_s_chip", "step_mem_GiB", "setup_s"}
    for name, metric in result["metrics"].items():
        assert metric["value"] > 0
        declared = next(m for m in BENCH["end_to_end"] if m["name"] == name)
        assert metric["unit"] == declared["unit"]
    assert result["device"]["memory_peak_bytes"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_traced(tiny_root, workload):
    """On the CPU there is no device plane: the device metrics' readers
    find nothing and are left out; the host's metric is there."""
    result = harness.run_cell(workload, seed=4, seconds=0.2, trace=True,
                              t_start=time.perf_counter(), root=tiny_root,
                              allow_cpu=True)
    assert result["correct"] is True
    assert result["metrics"]["host_dispatch_ms"]["value"] > 0
    assert "busy_s" not in result["device"]
    declared = {m["name"] for m in BENCH["per_layer"]}
    assert set(result["metrics"]) <= declared


def test_same_seed_same_inputs_and_first_loss(tiny_root, capsys):
    losses = []
    for _ in range(2):
        harness.run_cell("gpt2s-t128", seed=7, seconds=0.05, trace=False,
                         t_start=time.perf_counter(), root=tiny_root,
                         allow_cpu=True)
        out = capsys.readouterr().out
        line = next(line for line in out.splitlines()
                    if "losses of the first steps" in line)
        losses.append(line[line.index("losses of"):line.index("; fenced")])
    assert losses[0] == losses[1]


def test_no_chip_is_a_non_zero_exit_and_no_result():
    """The command itself never stands the CPU in for the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2s-t128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert run.returncode != 0
    assert "{" not in run.stdout


def test_every_per_layer_metric_has_its_reader_and_agrees():
    import importlib

    for entry in BENCH["per_layer"]:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{entry['name']}")
        assert reader.LAYER == entry["layer"]
        assert reader.UNIT == entry["unit"]
        assert callable(reader.read)


NEW_METRIC = '''"""Steps the traced window dispatched (a count)."""
LAYER = "Entry point and host loop"
UNIT = "steps"


def read(ctx):
    return float(ctx.steps)
'''


def test_a_configuration_a_cell_and_a_layer_metric_are_added_as_files(
        tmp_path):
    """Only new files and new entries: a wider decoder, a traffic mix of
    its own, a cell of the two, and a per-layer metric with its reader.
    The harness of the copy runs the cell as it stands."""
    root = tiny.make_tiny_copy(str(tmp_path))
    bench = os.path.join(root, "benchmark")
    before = {}
    for folder, _, files in os.walk(bench):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    with open(os.path.join(bench, "configs", "gpt2s.json")) as f:
        config = json.load(f)
    config.update(name="wider", n_embd=96, n_head=6)
    with open(os.path.join(bench, "configs", "wider.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", "t32-b4.json"), "w") as f:
        json.dump(dict(kind="token_batches", batch_per_chip=4, seq_len=32,
                       steps_per_chunk=2, chunks_queued=3, warmup_steps=1,
                       trace_steps=4), f)
    with open(os.path.join(bench, "layer_metrics", "steps_traced.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entries = json.load(f)
    entries["configs"].append(dict(
        name="wider", source="test", reduced=[], why="test",
        file="benchmark/configs/wider.json"))
    entries["workloads"].append(dict(
        name="wider-t32", config="wider", traffic="t32-b4", chips=1,
        why="test"))
    entries["per_layer"].append(dict(
        name="steps_traced", unit="steps", better="higher",
        source="program_counter", layer="Entry point and host loop",
        moves="samples_per_s_chip", workloads=["wider-t32"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(entries, f)

    # The copy's own harness, in a process of its own (this one has the
    # repository's benchmark package imported already).
    code = (
        "import json, sys, time; t = time.perf_counter();"
        "import jax; jax.config.update('jax_num_cpu_devices', 4);"
        "from benchmark import harness;"
        "assert harness.ROOT == sys.argv[1], harness.ROOT;"
        "r = [harness.run_cell('wider-t32', 1, 0.1, tr, t, allow_cpu=True)"
        "     for tr in (False, True)];"
        "print(json.dumps(r))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, ROOT]))
    run = subprocess.run([sys.executable, "-c", code, root], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    untraced, traced = json.loads(run.stdout.splitlines()[-1])
    assert untraced["correct"] and traced["correct"]
    assert untraced["metrics"]["samples_per_s_chip"]["value"] > 0
    assert traced["metrics"]["steps_traced"] == {"value": 4.0,
                                                 "unit": "steps"}
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"


def test_the_window_keeps_chunks_queued_behind_the_one_it_waits_for():
    """The host fences a chunk only while the next is already dispatched
    (so a slow host leaves no gap on the device), the chunk that fills
    the queue is not counted, and the window ends on a whole chunk."""
    events = []

    class Loss:
        def __init__(self, step):
            self.step = step

        def block_until_ready(self):
            time.sleep(0.01)
            events.append(("fence", self.step))

        def __float__(self):
            return 1.0 / self.step

    class Job:
        steps = 0

        def step(self):
            self.steps += 1
            events.append(("dispatch", self.steps))
            return Loss(self.steps)

    job = Job()
    marks, dispatch_max_s, last = harness.measure_window(
        job, seconds=0.05, steps_per_chunk=3, chunks_queued=2)
    fences = [i for i, e in enumerate(events) if e[0] == "fence"]
    for i in fences[:-1]:
        dispatched = max(s for kind, s in events[:i] if kind == "dispatch")
        assert dispatched >= events[i][1] + 3  # a whole chunk behind it
    assert events[fences[-1]] == ("fence", job.steps)  # drained
    assert len(marks) == len(fences) == job.steps // 3
    assert 0.05 <= marks[-1] - marks[0] < 0.05 + 2 * 0.011 + 0.01
    assert marks == sorted(marks) and dispatch_max_s >= 0
    assert last == 1.0 / job.steps


@pytest.mark.parametrize("late", [
    {}, {0: 0.3}, {9: 0.07}, {0: 0.02, 1: 0.05, 8: 0.004, 9: 0.3},
    {k: 0.01 * (k % 4) for k in range(10)}])
def test_the_chunk_period_is_the_slope_of_the_line_under_the_marks(late):
    """Marks the host saw late (never early) do not move the period as
    long as two marks well apart were seen on time."""
    marks = [100.0 + 1.2 * k + late.get(k, 0.0) for k in range(10)]
    assert harness.chunk_period(marks) == pytest.approx(1.2, rel=1e-9)


def test_the_chunk_period_counts_a_delay_that_comes_back():
    """A device that stops for 0.1 s after every third chunk is slower by
    that much on average, and the line under the marks says so; with
    two marks it is their distance."""
    marks = [1.2 * k + 0.1 * (k // 3) for k in range(10)]
    assert harness.chunk_period(marks) == pytest.approx(1.2 + 0.1 / 3)
    assert harness.chunk_period([5.0, 6.5]) == pytest.approx(1.5)
