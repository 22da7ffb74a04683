"""From the scopes the step programs write to device time per scope.

The program names its own work (docs/diagnostics.md, "Tracing"): every
HLO instruction's ``op_name`` is the path of ``jax.named_scope``s it was
issued under, ``jit(hvd_decoder_step)/transpose(jvp(forward))/while/body/
closed_call/while/body/closed_call/mlp/btf,fd->btd/dot_general``. What a
TPU v5e trace does with it on jax 0.9 / libtpu 0.0.34 (looked at by hand
in PR 24): the event's name is the instruction's text *without* its
``metadata={...}``; the ``op_name`` is the stat ``tf_op`` of the event's
*metadata* (xprof's convention: ``<op_name>:<op type>``, the type empty),
one name per instruction, a fusion's being its root's — no ``a;b`` list.
The same metadata holds ``hlo_category`` (``convolution fusion``, ``loop
fusion``, ``custom-call``, ``data formatting``, ...), ``flops``,
``bytes_accessed``, ``source`` and ``source_stack``. Instructions the
compiler made itself (``copy-done``, layout copies) have no ``tf_op``.
A Mosaic kernel's instruction is named after the ``pallas_call``'s
``name`` (``%flash_fwd.7``), and that name is the last scope of its path.

``jax.profiler.ProfileData`` gives an event's own stats and not its
metadata's, so the metadata of the first chip's plane is read here from
the ``.xplane.pb``'s bytes (protobuf wire format, the few fields needed)
and joined to the events ``trace_reduce.load`` kept by the instruction's
name. Times are those events', on that clock.
"""

import glob
import os
import re

from benchmark import harness, trace_reduce

FORWARD, BACKWARD = "forward", "backward"
EXCHANGE, OPTIMIZER, UNSCOPED = "exchange", "optimizer", "unscoped"
_FORWARD_SEGMENT = re.compile(r"\bforward\b")
# flax's module names in a path, by class of module (for the report).
_FLAX_MODULE = re.compile(r"(Conv|conv|BatchNorm|bn|Dense)_\w+$")
_FLAX_KINDS = {"Conv": "Conv", "conv": "Conv", "BatchNorm": "BatchNorm",
               "bn": "BatchNorm", "Dense": "Dense"}

# ---- the .xplane.pb's bytes -------------------------------------------------
# XSpace{planes=1}; XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5}
# (both maps: entry{key=1, value=2}); XEventMetadata{name=2, stats=5};
# XStatMetadata{id=1, name=2}; XStat{metadata_id=1, double=2, uint64=3,
# int64=4, str=5, bytes=6, ref=7 (a stat metadata's id: its name is the
# value)}.

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: varints as
    numbers, length-delimited fields as views of their bytes, fixed
    fields as their bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == _VARINT:
            value, at = _varint(buf, at)
        elif kind == _BYTES:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (_FIXED64, _FIXED32):
            size = 8 if kind == _FIXED64 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, kind, value


def _map_values(entries):
    for entry in entries:
        for number, _, value in _fields(entry):
            if number == 2:
                yield value


def metadata_stat(path, plane_name, stat="tf_op"):
    """{instruction name: value of ``stat``} over the event metadata of
    the plane ``plane_name`` of the trace at ``path``.
    The instruction name is the event name's first word, ``%fusion.182``:
    unique in a program, and the window runs one program."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, kind, plane in _fields(space):
        if number != 1 or kind != _BYTES:
            continue
        name, events, stats = None, [], []
        for number, kind, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(value)
            elif number == 5:
                stats.append(value)
        if name != plane_name:
            continue
        stat_names = {}
        for meta in _map_values(stats):
            fields = {n: v for n, _, v in _fields(meta)}
            stat_names[fields.get(1)] = bytes(fields.get(2, b"")).decode()
        found = {}
        for meta in _map_values(events):
            instruction, value = None, None
            for number, kind, field in _fields(meta):
                if number == 2:
                    instruction = bytes(field).decode().split(" ")[0]
                elif number == 5:
                    fields = {n: v for n, _, v in _fields(field)}
                    if stat_names.get(fields.get(1)) != stat:
                        continue
                    if 7 in fields:
                        value = stat_names.get(fields[7], "")
                    elif 5 in fields:
                        value = bytes(fields[5]).decode()
                    else:
                        value = fields.get(3, fields.get(4))
            if instruction and value not in (None, ""):
                found.setdefault(instruction, value)
        return found
    return {}


# ---- scope paths ------------------------------------------------------------

def scope_path(tf_op):
    """``op_name`` of xprof's ``<op_name>:<op type>``."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def segments(path):
    """The scopes of a path, outermost first: ``a/b(c)/d`` is a, b(c), d.
    Of ``;``-joined names the first counts."""
    return path.split(";")[0].split("/")


def classify(path):
    """Which of the step's disjoint classes an instruction belongs to,
    from its scope path. ``exchange`` and ``optimizer`` first, wherever
    they are; then the pass: the segment that names ``forward`` is
    ``jvp(forward)`` in the forward pass and has ``transpose(`` around
    it in the backward pass (rematerialised work under
    ``.../checkpoint/rematted_computation/...`` is in the pass that runs
    it); a path that names none of them is ``unscoped``."""
    parts = segments(path)
    if EXCHANGE in parts:
        return EXCHANGE
    if OPTIMIZER in parts:
        return OPTIMIZER
    for part in parts:
        if _FORWARD_SEGMENT.search(part):
            return BACKWARD if "transpose(" in part else FORWARD
    return UNSCOPED


def newest_xplane():
    """The trace this process wrote: one process runs one cell, and the
    harness keeps only the newest trace of a cell."""
    found = glob.glob(os.path.join(harness.ROOT, ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def scoped_events(ctx, path=None):
    """[(name, start_ns, duration_ns, scope path)] of the leaf operations
    of the first chip's ``XLA Ops`` line that touch the steady window
    (``attach``), with the paths of the run's trace file. Parsed once per
    run and kept on ``ctx``."""
    if not hasattr(ctx, "scoped_events"):
        path = path or newest_xplane()
        paths = {}
        if ctx.window and path:
            plane = f"/device:TPU:{ctx.planes[0][0]}"
            paths = {name: scope_path(tf_op) for name, tf_op
                     in metadata_stat(path, plane).items()}
        ctx.scoped_events = attach(ctx, paths)
    return ctx.scoped_events


def attach(ctx, paths):
    """The events of ``ctx``, in its names and times, each with the scope
    path ``paths`` has for its instruction ('' for none). None where
    there is no device plane or no event whose path names a scope of the
    vocabulary: the CPU's rehearsal, no trace file, a program without
    scopes, an executable read back from a compile cache that a build
    without them had filled."""
    if not ctx.window:
        return None
    events = [(name, start, dur, paths.get(name.split(" ")[0], ""))
              for name, start, dur in trace_reduce.op_events(ctx.lines,
                                                            ctx.window)]
    if all(classify(e[3]) == UNSCOPED for e in events):
        return None
    return events


def per_step_ms(ctx, select):
    """Device time per step, in ms, of the scoped events ``select(name,
    scope path)`` accepts: the union of their intervals inside the steady
    window over the steps in it. None where there are no scoped events
    or none is accepted — never a zero for what was not there."""
    events = scoped_events(ctx)
    if not events:
        return None
    mine = [(start, start + dur) for name, start, dur, path in events
            if select(name, path)]
    if not mine:
        return None
    return ctx.per_step_ms(trace_reduce.total(trace_reduce.clip(
        trace_reduce.union(mine), ctx.window)))


def class_ms(ctx, which):
    return per_step_ms(ctx, lambda name, path: classify(path) == which)


def scope_ms(ctx, *scopes):
    """Time under any of ``scopes``, in either direction."""
    return per_step_ms(
        ctx, lambda name, path: not set(scopes).isdisjoint(segments(path)))


def kernel_ms(ctx, scope):
    """Time of the Mosaic custom calls under ``scope``."""
    return per_step_ms(
        ctx, lambda name, path: trace_reduce.is_mosaic_kernel(name)
        and scope in segments(path))


# ---- a trace read by hand ---------------------------------------------------

def report(path, top=5):
    """Lines that say where a traced step's device time went by scope:
    the classes, the decoder's blocks and kernels, the flax modules by
    class of module and direction, the ``unscoped`` remainder by raw
    name, and the time by ``hlo_category``. For PERF.md's section 5:
    ``python3 -m benchmark.scope_reduce <trace.xplane.pb>`` from the root
    of the checkout."""
    ctx = trace_reduce.Context(trace=trace_reduce.load(path), chips=1,
                               steps=None, dispatch_s=None, job=None,
                               peaks=None)
    events = scoped_events(ctx, path)
    if not events:
        return ["no device plane, or no event under a scope"]
    plane = f"/device:TPU:{ctx.planes[0][0]}"
    category = metadata_stat(path, plane, "hlo_category")
    steps = len(trace_reduce.step_events(ctx.lines))

    def ms(select):
        return per_step_ms(ctx, select) or 0.0

    out = [f"{steps} steps, step_device_ms {ctx.step_device_ms():.3f}"]
    for which in (FORWARD, BACKWARD, OPTIMIZER, EXCHANGE, UNSCOPED):
        out.append(f"  {which:10s} {class_ms(ctx, which) or 0.0:9.3f} ms")
    blocks = sorted({s for e in events for s in segments(e[3])} & {
        "embed", "attention", "mlp", "moe", "head", "loss", "flash_fwd",
        "flash_dq", "flash_dkv", "flash_xla"})
    for block in blocks:
        row = [ms(lambda n, p, d=d: block in segments(p)
                  and classify(p) == d) for d in (FORWARD, BACKWARD)]
        out.append(f"  {block:10s} forward {row[0]:8.3f} backward "
                   f"{row[1]:8.3f} ms")
    kinds = {}
    for name, start, dur, p in events:
        flax = [m.group(1) for m in map(_FLAX_MODULE.match, segments(p))
                if m]
        if flax and classify(p) in (FORWARD, BACKWARD):
            key = (_FLAX_KINDS[flax[-1]], classify(p))
            kinds[key] = kinds.get(key, 0.0) + dur
    for (kind, direction), ns in sorted(kinds.items()):
        out.append(f"  module {kind:9s} {direction:8s} "
                   f"{ns / steps / 1e6:8.3f} ms")
    by_category, raw = {}, {}
    for name, start, dur, p in events:
        what = category.get(name.split(" ")[0], "none")
        by_category[what] = by_category.get(what, 0.0) + dur
        if classify(p) == UNSCOPED:
            raw[name] = raw.get(name, 0.0) + dur
    for what, ns in sorted(by_category.items(), key=lambda kv: -kv[1]):
        out.append(f"  hlo_category {what:24s} {ns / steps / 1e6:8.3f} ms")
    for name, ns in sorted(raw.items(), key=lambda kv: -kv[1])[:top]:
        out.append(f"  unscoped {ns / steps / 1e6:8.3f} ms {name}")
    return out


if __name__ == "__main__":
    import sys

    print("\n".join(report(sys.argv[1])))
