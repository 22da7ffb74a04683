"""The per-layer metrics that move ``setup_s`` (PR 34): ten entries of
BENCHMARK.json found by name, their readers rehearsed on the CPU in a
process of their own at tiny sizes (one decoder cell, ``resnet50-1chip``),
and what they do over a program that records no spans."""
import importlib
import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchmark.layer_metrics import setup_in_program_s as setup
from benchmark.tests import tiny

ROOT = tiny.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
RESNET = [c for c in CELLS if c.startswith("resnet50")]
DECODERS = [c for c in CELLS if c not in RESNET]
HOST, STEP = "Entry point and host loop", "Step program"
# The parts are disjoint self times and add up to setup_in_program_s.
PARTS = {
    "setup_import_s": (HOST, CELLS),
    "setup_init_s": (HOST, CELLS),
    "setup_native_core_s": (HOST, RESNET),
    "setup_state_s": (HOST, CELLS),
    "setup_step_trace_s": (STEP, CELLS),
    "setup_step_lower_s": (STEP, CELLS),
    "setup_step_load_s": (STEP, CELLS),
    "setup_other_programs_s": (HOST, CELLS),
}
ENTRIES = dict(PARTS, setup_kernels_traced=("Kernels", DECODERS),
               setup_in_program_s=(HOST, CELLS))


@pytest.mark.parametrize("name", ENTRIES)
def test_the_entry_and_its_reader(name):
    layer, cells = ENTRIES[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    unit = "x" if name == "setup_kernels_traced" else "s"
    assert entry == dict(name=name, unit=unit, better="lower",
                         source="host_clock", layer=layer, moves="setup_s",
                         workloads=cells)
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT) == (layer, unit)
    assert reader.__doc__ and callable(reader.read)


def test_nothing_else_moves_set_up_and_set_up_is_an_end_to_end_metric():
    assert {m["name"] for m in BENCH["per_layer"]
            if m["moves"] == "setup_s"} == set(ENTRIES)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


_REHEARSAL = """
    import json, sys, time
    T_START = time.perf_counter()
    sys.path.insert(0, {root!r})
    import jax
    jax.config.update("jax_num_cpu_devices", 4)
    from benchmark import harness
    from benchmark.tests import tiny

    result = harness.run_cell({cell!r}, 5, 0.2, True, T_START,
                              root=tiny.make_tiny_copy(sys.argv[1]),
                              allow_cpu=True)
    age = time.perf_counter() - T_START
    from horovod_tpu.common import metrics
    print(json.dumps(dict(
        result=result, age=age, counters=metrics.counters(),
        threads=len({{r["thread"] for r in metrics.spans()}}))))
"""


@pytest.fixture(scope="module", params=["gpt2s-t128", "resnet50-1chip"])
def rehearsal(request, tmp_path_factory):
    """A traced run of the cell on the CPU at tiny sizes, in a fresh
    process as a run on the chip is: its imports are in the record."""
    where = tmp_path_factory.mktemp("setup")
    script = where / "rehearse.py"
    (where / "tiny").mkdir()
    script.write_text(textwrap.dedent(_REHEARSAL).format(
        root=ROOT, cell=request.param))
    done = subprocess.run(
        [sys.executable, str(script), str(where / "tiny")], cwd=str(where),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    return request.param, json.loads(lines[-1]), lines


def test_a_traced_rehearsal_reports_what_its_cell_should(rehearsal):
    cell, out, _ = rehearsal
    assert out["result"]["correct"] is True
    values = {name: m["value"] for name, m in out["result"]["metrics"].items()
              if name.startswith("setup_")}
    # Off the chip the tiny decoder runs the kernels' XLA twins: the host
    # traced no pallas_call, the program counted none, the reader has
    # nothing to read (tests/test_setup_spans.py holds the counters).
    assert not any(k.startswith("kernels.traced.") for k in out["counters"])
    assert set(values) == {name for name, (_, cells) in ENTRIES.items()
                           if cell in cells} - {"setup_kernels_traced"}
    assert all(v >= 0 for v in values.values())
    # One thread did the work: the disjoint parts are the union.
    assert out["threads"] == 1
    parts = sum(values[name] for name in PARTS if name in values)
    assert parts == pytest.approx(values["setup_in_program_s"], rel=1e-6)
    assert values["setup_in_program_s"] < out["age"]
    for unit_of in ("setup_import_s", "setup_in_program_s"):
        assert out["result"]["metrics"][unit_of]["unit"] == "s"


def test_the_inside_reading_agrees_with_the_harness_stamp(rehearsal):
    """The three phases of the step module against the harness's own
    "step compiled or read back in ...s" of the same run."""
    _, out, lines = rehearsal
    stamp = next(line for line in lines if "step compiled or read back" in line)
    outside = float(stamp.split("read back in ")[1].split("s;")[0])
    values = out["result"]["metrics"]
    inside = sum(values[name]["value"] for name in
                 ("setup_step_trace_s", "setup_step_lower_s",
                  "setup_step_load_s"))
    assert inside <= outside + 0.01
    assert outside - inside < max(0.2, 0.03 * outside)


def test_over_a_program_without_spans_every_reader_returns_nothing(
        monkeypatch):
    """The driver lays these files over the parent's checkout: there
    ``common/metrics.py`` has counters and no ``spans``."""
    import horovod_tpu.common.metrics as program_metrics

    monkeypatch.delattr(program_metrics, "spans")
    monkeypatch.setattr(program_metrics, "counters", lambda: {"x.y": 3})
    for name in ENTRIES:
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(None) is None, name


def test_a_span_no_metric_reads_counts_in_no_part():
    spans = [dict(id=0, parent=None, name="probe"),
             dict(id=1, parent=0, name="jaxpr_trace:jit_hvd_dp_step"),
             dict(id=2, parent=1, name="state.init"),
             dict(id=3, parent=0, name="backend_compile:jit__lambda"),
             dict(id=4, parent=None, name="native.build"),
             dict(id=5, parent=None, name="import:horovod_tpu.models")]
    by_id = {r["id"]: r for r in spans}
    assert [setup.part_of(r, by_id) for r in spans] == [
        None, "step_trace", "step_trace", "other_programs", "native_core",
        "import"]
