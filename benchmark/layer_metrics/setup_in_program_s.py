"""What of ``setup_s`` the program and jax's compile phases account for:
the union, on the clock, of every set-up span the process recorded
(``horovod_tpu.common.metrics.spans()``: the program's own at each
boundary a job crosses before its first step, jax's trace, lowering and
compile-or-cache-read of every program, all threads). ``setup_s`` less
it is Python's start, jax's import, the TPU runtime's claim of the chip
and the benchmark's own work between programs.

The other ``setup_*`` readers take their part from here: every span
belongs to one part, by its name or, inside one of a step module's three
phases, by that phase; a part is the sum of its spans' self times (a
span's duration less what its children cover), so the parts are disjoint
and, where one thread did the work, add up to this metric. Nothing for a
program that records no spans."""
import horovod_tpu.common.metrics as program_metrics

LAYER = "Entry point and host loop"
UNIT = "s"

# docs/diagnostics.md, "Tracing": the jitted modules of the step builders.
STEP_MODULES = ("jit_hvd_dp_step", "jit_hvd_decoder_step",
                "jit_hvd_decoder_bias_step", "jit_hvd_zero_step")
PHASES = {"jaxpr_trace": "step_trace", "jaxpr_to_mlir_module": "step_lower",
          "backend_compile": "step_load"}
OWN = {"init": "init", "engine.start": "init", "mesh": "init",
       "step.build": "state"}
PREFIXES = {"import:": "import", "native.": "native_core", "state.": "state"}


def records():
    spans = getattr(program_metrics, "spans", None)
    return spans() if spans else []


def part_of(record, by_id):
    """The part a span's self time counts in, None for a name no metric
    reads."""
    inside = record
    while inside is not None:
        phase, _, module = inside["name"].partition(":")
        if phase in PHASES and module in STEP_MODULES:
            return PHASES[phase]
        inside = by_id.get(inside["parent"])
    name = record["name"]
    if name.partition(":")[0] in PHASES:
        return "other_programs"
    return OWN.get(name) or next(
        (part for prefix, part in PREFIXES.items()
         if name.startswith(prefix)), None)


def part(which):
    """Seconds of self time in the part ``which``, None if no span is."""
    spans = records()
    by_id = {r["id"]: r for r in spans}
    own = [r["self_ns"] for r in spans if part_of(r, by_id) == which]
    return sum(own) / 1e9 if own else None


def read(ctx):
    total, reach = 0, None
    for r in sorted(records(), key=lambda r: r["start_ns"]):
        if reach is None or r["start_ns"] > reach:
            total, reach = total + r["end_ns"] - r["start_ns"], r["end_ns"]
        elif r["end_ns"] > reach:
            total, reach = total + r["end_ns"] - reach, r["end_ns"]
    return total / 1e9 if reach is not None else None
