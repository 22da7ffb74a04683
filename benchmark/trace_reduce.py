"""From a profiler trace to numbers. Part of the yardstick.

``load`` reads the ``.xplane.pb`` jax's profiler writes with
``jax.profiler.ProfileData`` (nothing but jax) into plain lists, which is
also the form the recorded trace under tests/data/ is kept in. All times
are nanoseconds on the trace's own clock.

What a TPU v5e trace holds on jax 0.9 / libtpu 0.0.34 (looked at by hand
in PR 23): one plane per chip, ``/device:TPU:<n>``, with the lines

- ``XLA Modules``: one event per execution of a compiled program, named
  ``jit_<function>(<fingerprint>)``;
- ``XLA Ops``: one event per HLO instruction the core executes, named by
  the instruction's whole text (``%fusion.182 = (bf16[768,50304]{...})
  fusion(...), kind=...``). Control flow nests: a ``while`` event spans
  the events of its body, so sums over this line count twice and only
  unions and leaf operations mean anything. A Mosaic kernel is a
  ``custom-call`` with ``custom_call_target="tpu_custom_call"``;
- ``Async XLA Ops``: one event per asynchronous pair, from the
  ``-start`` instruction to its ``-done`` (copies, slices, and the
  collectives that are asynchronous), running beside the core's own;
- ``Steps``: the module events again, numbered.

Host threads are lines of the plane ``/host:CPU``, on the same clock.
``load`` shortens an instruction's text to ``%name opcode shape`` (plus
the target of a custom call): nothing else of it is read.
"""

import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# The spans harness.trace_window writes around its own loop.
HOST_SPANS = ("bench.dispatch", "bench.fence")
# Instructions whose event spans the events of a computation they call.
CONTAINERS = ("while", "conditional", "call")

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def shorten(text):
    """``%name opcode shape [target]`` of an HLO instruction's text; any
    other event name (a module, a host span) as it is."""
    name, eq, rest = text.partition(" = ")
    if not eq or not name.startswith("%"):
        return text
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.search(rest)
    target = _TARGET.search(rest)
    return " ".join(filter(None, (
        name, opcode.group(1) if opcode else "?",
        shape.group(0) if shape else "", target.group(1) if target else "")))


def opcode(name):
    """The opcode of a shortened instruction name ('' for other events)."""
    parts = name.split(" ")
    return parts[1] if name.startswith("%") and len(parts) > 1 else ""


def load(path):
    """``{plane name: {line name: [(event name, start_ns, duration_ns),
    ...]}}`` of every device plane, and of the host plane the benchmark's
    own spans only (a host thread's line holds thousands of runtime
    events nobody reads)."""
    from jax.profiler import ProfileData

    trace = {}
    for plane in ProfileData.from_file(path).planes:
        is_device = DEVICE_PLANE.match(plane.name)
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = {}
        for line in plane.lines:
            events = [(shorten(e.name), float(e.start_ns),
                       float(e.duration_ns))
                      for e in line.events
                      if is_device or e.name in HOST_SPANS]
            if events:
                lines.setdefault(line.name, []).extend(events)
        trace[plane.name] = lines
    return trace


# ---- interval arithmetic ---------------------------------------------------

def union(intervals):
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(intervals, holes):
    """The part of disjoint sorted ``intervals`` not covered by disjoint
    sorted ``holes``."""
    out = []
    holes = list(holes)
    for start, end in intervals:
        at = start
        for h0, h1 in holes:
            if h1 <= at:
                continue
            if h0 >= end:
                break
            if h0 > at:
                out.append((at, h0))
            at = max(at, h1)
            if at >= end:
                break
        if at < end:
            out.append((at, end))
    return out


def spans(events):
    return [(start, start + dur) for _, start, dur in events]


# ---- what the device did ---------------------------------------------------

def device_planes(trace):
    """Device planes in chip order: [(ordinal, lines)]."""
    found = []
    for name, lines in trace.items():
        m = DEVICE_PLANE.match(name)
        if m:
            found.append((int(m.group(1)), lines))
    return sorted(found, key=lambda p: p[0])


def step_events(lines):
    """The executions of the step program on one chip: the events of the
    module that took most time on the ``XLA Modules`` line."""
    by_name = {}
    for event in lines.get(MODULES_LINE, []):
        by_name.setdefault(event[0], []).append(event)
    if not by_name:
        return []
    return max(by_name.values(), key=lambda evs: sum(e[2] for e in evs))


def steady_window(lines):
    """From the start of the first traced step to the end of the last:
    what the profiler does before and after is not the program's idle
    time."""
    steps = step_events(lines)
    if not steps:
        return None
    return (min(s for _, s, _ in steps), max(s + d for _, s, d in steps))


def op_events(lines, window=None, match=None, line=OPS_LINE, leaves=True):
    """Operations of one line, optionally those that touch ``window`` and
    whose name satisfies ``match``. ``leaves`` leaves out the containers
    (``while`` and the like), whose events span their bodies' events."""
    events = lines.get(line, [])
    if leaves:
        events = [e for e in events if opcode(e[0]) not in CONTAINERS]
    if window:
        events = [e for e in events
                  if e[1] + e[2] > window[0] and e[1] < window[1]]
    if match:
        events = [e for e in events if match(e[0])]
    return events


def busy_intervals(lines, window):
    """Union of the intervals in which the core ran an operation, loops
    included: between two operations of a loop's body the core is
    running the loop, not waiting for the host."""
    return clip(union(spans(op_events(lines, window, leaves=False))), window)


def busy_ns(lines, window):
    return total(busy_intervals(lines, window))


def matching_ns(lines, window, match):
    """Time in operations whose name satisfies ``match``, on the core's
    line or the asynchronous one: (all of it, the part during which the
    core ran no other operation — the exposed part)."""
    mine = clip(union(spans(
        op_events(lines, window, match)
        + op_events(lines, window, match, ASYNC_LINE))), window)
    others = clip(union(spans(op_events(
        lines, window, lambda name: not match(name)))), window)
    return total(mine), total(subtract(mine, others))


def idle_gaps(lines, window):
    return subtract([window], busy_intervals(lines, window))


def is_all_reduce(name):
    """``all-reduce``, or its asynchronous ``-start`` / ``-done``."""
    return opcode(name).startswith("all-reduce")


def is_mosaic_kernel(name):
    return opcode(name) == "custom-call" and name.endswith("tpu_custom_call")


class Context:
    """What a per-layer metric's reader gets: the parsed trace and the
    facts of the run. Readers take device metrics from the first chip
    (``self.lines``) unless they say otherwise."""

    def __init__(self, trace, chips, steps, dispatch_s, job, peaks):
        self.trace, self.chips, self.steps = trace, chips, steps
        self.dispatch_s, self.job, self.peaks = dispatch_s, job, peaks
        self.planes = device_planes(trace)[:chips]
        self.lines = self.planes[0][1] if self.planes else None
        self.window = steady_window(self.lines) if self.lines else None

    def per_step_ms(self, ns):
        return ns / len(step_events(self.lines)) / 1e6

    def step_device_ms(self):
        if not self.window:
            return None
        return statistics.median(d for _, _, d in step_events(self.lines)) \
            / 1e6

    def busy_and_window_s(self):
        """(busy, window) seconds averaged over the chips used, each chip
        over its own steady window."""
        pairs = []
        for _, lines in self.planes:
            window = steady_window(lines)
            if window:
                pairs.append((busy_ns(lines, window), window[1] - window[0]))
        if not pairs:
            return None
        return (sum(b for b, _ in pairs) / len(pairs) / 1e9,
                sum(w for _, w in pairs) / len(pairs) / 1e9)

    def host_spans(self):
        found = []
        for events in self.trace.get(HOST_PLANE, {}).values():
            found.extend(e for e in events if e[0] in HOST_SPANS)
        return found

    def breakdown(self, top=10):
        """The operations that took most device time on the first chip,
        under the names the trace gives, and the longest idle gaps named
        by the host span of the benchmark's loop that covers most of
        each (seconds)."""
        by_name = {}
        for name, start, dur in op_events(self.lines, self.window):
            by_name[name] = by_name.get(name, 0.0) + dur
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        host = self.host_spans()
        gaps = []
        for g0, g1 in sorted(idle_gaps(self.lines, self.window),
                             key=lambda g: g[0] - g[1])[:top]:
            cover = {}
            for name, start, dur in host:
                overlap = min(g1, start + dur) - max(g0, start)
                if overlap > 0:
                    cover[name] = cover.get(name, 0.0) + overlap
            name = max(cover, key=cover.get) if cover else "no-host-span"
            gaps.append([name, (g1 - g0) / 1e9])
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": gaps}
