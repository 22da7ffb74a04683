"""The harness behind ``benchmark/run.py``: finds a cell's files by the
names in ``BENCHMARK.json``, sets the job up, measures one window and
prints the result line. Everything that belongs to one configuration,
traffic mix, runner or per-layer metric lives in a file of its own
(README.md); nothing here names one."""

import collections
import glob
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILED = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """The machine does not hold what the cell asks for: no result."""


class Narrator:
    """Prints information lines stamped with the seconds since the
    process started: where set-up goes is read off these."""

    def __init__(self, t_start):
        self.t_start = t_start

    def __call__(self, msg):
        print(f"[bench] +{time.perf_counter() - self.t_start:6.2f}s {msg}",
              flush=True)


def _read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload, root=ROOT):
    """The cell ``workload`` of ``root``/BENCHMARK.json with its
    configuration and traffic files, and the metrics it reports."""
    bench = _read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    bench_dir = os.path.join(root, bench["paths"][0])

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    return dict(
        cell=cell,
        config=_read_json(root, config_entry["file"]),
        traffic=_read_json(bench_dir, "workloads", cell["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if reported(m)],
        per_layer=[m for m in bench["per_layer"] if reported(m)])


def enable_compile_cache(root=ROOT):
    """jax's persistent cache at the place ``tools/compile_cache.py``
    uses: ``JAX_COMPILATION_CACHE_DIR`` where the machine sets it,
    otherwise the fixed ``.jax_cache/`` of this checkout (the path is
    part of the cache key). Every program is kept, however quick its
    compile: set-up is paid by every run of every later check."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileWatch:
    """Counts jax's own compile events (``chip_smoke.py``'s method):
    every program jax lowers is then compiled or read from the cache, so
    no lowering inside the window is no compilation inside it."""

    def __init__(self):
        import jax.monitoring

        self.lowered = self.compiled = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _LOWERED:
            self.lowered += 1
        elif event == _COMPILED:
            self.compiled += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def claim_devices(chips, allow_cpu=False):
    """The first ``chips`` devices and the device as jax reports it. No
    TPU, a TPU that is not in the peak table, or fewer chips than the
    cell asks for: no result, exit code 3. ``allow_cpu`` is for the
    tests' rehearsals, never reachable from the command line."""
    import jax

    from benchmark import flops

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if devs[0].platform != "tpu" and not allow_cpu:
        print(f"benchmark: jax found no TPU (platform {devs[0].platform!r}); "
              f"a device number comes only from a chip run",
              file=sys.stderr)
        raise NoChip(3)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chip(s), jax reports "
              f"{len(devs)}", file=sys.stderr)
        raise NoChip(3)
    peaks = None if allow_cpu and devs[0].platform != "tpu" else \
        flops.peaks_for(devs[0].device_kind)
    return devs[:chips], device, peaks


def step_program_bytes(compiled):
    """Bytes the step program holds on one device: arguments +
    temporaries + outputs - aliased (donated arguments the outputs
    reuse), from the compiler's own accounting of this executable."""
    mem = compiled.memory_analysis()
    parts = dict(arguments=mem.argument_size_in_bytes,
                 temporaries=mem.temp_size_in_bytes,
                 outputs=mem.output_size_in_bytes,
                 aliased=mem.alias_size_in_bytes)
    total = (parts["arguments"] + parts["temporaries"] + parts["outputs"]
             - parts["aliased"])
    return total, parts


def runtime_peak_bytes(devices):
    """``peak_bytes_in_use`` on the fullest device, as the runtime counts
    it (on this runtime that leaves program temporaries out: PERF.md)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure_window(job, seconds, steps_per_chunk, chunks_queued):
    """Steps in chunks of ``steps_per_chunk`` with ``chunks_queued`` of
    them dispatched ahead, as a training loop that reads its losses a
    little behind: the host waits for a chunk's last loss only while the
    chunks after it are already queued, so a host that is slow to wake
    or to dispatch (its cores may be shared) leaves no gap on the
    device. A mark is the time a chunk was seen to end. The first chunk
    ends with the queue full and counts as set-up; the window runs from
    its mark to the last, whole chunks only, and nothing more is
    dispatched once what is queued will end past ``seconds``. Returns
    the marks, the longest time the host took to dispatch a chunk, and
    the last loss."""
    queued = collections.deque()
    marks, dispatch_s = [], []
    more = True
    while more or queued:
        while more and len(queued) < chunks_queued:
            t0 = time.perf_counter()
            for _ in range(steps_per_chunk):
                loss = job.step()
            dispatch_s.append(time.perf_counter() - t0)
            queued.append(loss)
        loss = queued.popleft()
        loss.block_until_ready()
        marks.append(time.perf_counter())
        if len(marks) > 1:
            window = marks[-1] - marks[0]
            chunk_s = window / (len(marks) - 1)
            more = more and window + len(queued) * chunk_s < seconds
    return marks, max(dispatch_s), float(loss)


def _turns_up(a, b, c):
    """Whether the way a -> b -> c bends upwards at b."""
    return ((b[0] - a[0]) * (c[1] - a[1])
            - (b[1] - a[1]) * (c[0] - a[0])) > 0


def chunk_period(marks):
    """Seconds the device takes for a chunk, from the times the host saw
    the chunks end. The device never waits, so it ends chunk k at
    a + k * period; the host sees that then or later (a host whose cores
    are shared wakes late, by up to 0.1 s where its time is rationed),
    never earlier. So the period is the slope of the line under the
    marks: of all lines with no mark below, the one the marks lie
    closest to in sum, which is the edge of their lower convex hull that
    spans the middle of the window. A delay at the window's edge, which
    total steps over total time takes whole, moves it only if no mark
    near that edge was seen on time; a delay that comes back at every
    chunk, or every few, is in the slope."""
    hull = []
    for point in enumerate(marks):
        while len(hull) > 1 and not _turns_up(hull[-2], hull[-1], point):
            hull.pop()
        hull.append(point)
    middle = (len(marks) - 1) / 2
    (k0, t0), (k1, t1) = next(edge for edge in zip(hull, hull[1:])
                              if edge[1][0] >= middle)
    return (t1 - t0) / (k1 - k0)


def trace_window(job, steps, trace_dir):
    """A short steady window under the profiler: ``steps`` steps
    dispatched back to back as in the measured window, one fence at the
    end, the loop's own host spans written beside the device's."""
    import jax
    from jax.profiler import TraceAnnotation

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the interpreter's calls: not needed
    options.host_tracer_level = 2
    dispatch_s = []
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        begin = time.perf_counter()
        for _ in range(steps):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                loss = job.step()
            dispatch_s.append(time.perf_counter() - t0)
        with TraceAnnotation("bench.fence"):
            loss.block_until_ready()
        whole = time.perf_counter() - begin
    finally:
        jax.profiler.stop_trace()
    return dispatch_s, whole, float(loss)


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    return found[-1]


def read_layer_metrics(entries, ctx):
    """Each per-layer metric is read by ``layer_metrics/<name>.py``'s
    ``read(ctx)``. A reader that finds nothing to read returns None and
    the metric is left out of the line."""
    out = {}
    for entry in entries:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{entry['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def compile_check_warm_up(job, traffic, say):
    """The rest of set-up once the job is built: compile the step ahead
    of time (the executable the window runs is the one whose memory is
    reported), hold the first step to the plain reference, warm up.
    Returns (step program bytes, reference checks, first losses)."""
    t0 = time.perf_counter()
    job.compiled = job.lower().compile()
    step_bytes, parts = step_program_bytes(job.compiled)
    say(f"step compiled or read back in {time.perf_counter() - t0:.2f}s; "
        f"program bytes per device {parts} = {step_bytes}")

    t0 = time.perf_counter()
    job.prepare_reference()
    warm = [float(job.step())]
    checks = job.compare_reference(warm[0])
    say(f"reference check in {time.perf_counter() - t0:.2f}s:")
    for c in checks:
        say(f"  {c}")
    warm_ms = []
    for _ in range(traffic["warmup_steps"]):
        t0 = time.perf_counter()
        warm.append(float(job.step()))
        warm_ms.append(round(1e3 * (time.perf_counter() - t0), 3))
    say(f"losses of the first steps {[round(x, 5) for x in warm]}; "
        f"fenced step_ms {warm_ms}")
    return step_bytes, checks, warm


def run_cell(workload, seed, seconds, trace, t_start, root=ROOT,
             allow_cpu=False):
    """Run one cell once; returns the result object (the last line) or
    raises :class:`NoChip`. ``t_start`` is the process's start on
    ``time.perf_counter``'s clock."""
    say = Narrator(t_start)
    spec = load_cell(workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = cell["chips"]
    cache_dir = enable_compile_cache(root)

    import numpy as np

    from benchmark import trace_reduce

    watch = CompileWatch()
    devices, device, peaks = claim_devices(chips, allow_cpu)
    say(f"{workload}: config {cell['config']} traffic {cell['traffic']} "
        f"seed {seed} on {device}; compile cache at {cache_dir}")

    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    say(f"runner {config['runner']} imported")
    job = runner.build(config, traffic, devices, seed)
    say("weights, optimizer state and the batch are on the device")
    try:
        step_bytes, checks, warm = compile_check_warm_up(job, traffic,
                                                          say)
        unit = f"{job.sample_unit}/s/chip"
        flops_per_sample = job.model_flops_per_step / job.samples_per_step
        say(f"model FLOPs per {job.sample_unit[:-1]} {flops_per_sample:.6g}")

        lowered, compiled = watch.lowered, watch.compiled
        setup_s = time.perf_counter() - t_start
        if trace:
            trace_dir = os.path.join(root, ".bench_trace", workload)
            shutil.rmtree(trace_dir, ignore_errors=True)  # keep the newest
            steps = traffic["trace_steps"]
            dispatch_s, window_s, last = trace_window(job, steps, trace_dir)
        else:
            per_chunk = traffic["steps_per_chunk"]
            marks, dispatch_max_s, last = measure_window(
                job, seconds, per_chunk, traffic["chunks_queued"])
            setup_s = marks[0] - t_start  # the queue is full from here
            steps = per_chunk * (len(marks) - 1)
            window_s = marks[-1] - marks[0]
        in_window = (watch.lowered - lowered, watch.compiled - compiled)
        rate = steps * job.samples_per_step / window_s / chips

        say(f"{'traced ' if trace else ''}window: {steps} steps in "
            f"{window_s:.4f}s = {rate:.2f} {unit}; loss {warm[-1]:.5f} -> "
            f"{last:.5f}; programs lowered, compiled in the window: "
            f"{in_window}")
        if not trace:
            # Held to the bound: the rate by the line under the marks.
            as_seen = rate
            period = chunk_period(marks)
            rate = per_chunk * job.samples_per_step / period / chips
            late = [m - marks[0] - period * k for k, m in enumerate(marks)]
            say(f"{rate:.2f} {unit} by the line under the {len(marks)} "
                f"marks ({100 * (rate / as_seen - 1):+.4f}% of first to "
                f"last); the host took at most {1e3 * dispatch_max_s:.1f} "
                f"ms to dispatch a chunk; ms each mark was seen after the "
                f"line {[round(1e3 * (x - min(late)), 1) for x in late]}")
            if peaks:
                mfu = rate * flops_per_sample / peaks["bf16_flops_per_s"]
                say(f"model FLOP/s utilization {100 * mfu:.2f}% of "
                    f"{peaks['bf16_flops_per_s']:.4g}")
        runtime_peak = runtime_peak_bytes(devices)
        say(f"memory: step program {step_bytes} bytes per device; "
            f"memory_stats peak_bytes_in_use on the fullest chip "
            f"{runtime_peak}")
        say(f"compiles this run: {watch.compiled} in {watch.compile_s:.1f}s,"
            f" {watch.cache_hits} read from the cache; set-up "
            f"{setup_s:.2f}s")

        correct = (all(c["ok"] for c in checks) and in_window == (0, 0)
                   and all(np.isfinite(warm + [last])) and last < warm[-1])
        device["memory_peak_bytes"] = max(step_bytes, runtime_peak or 0)
        result = {"correct": bool(correct), "attempted": steps,
                  "failed": 0 if np.isfinite(last) else steps,
                  "metrics": {}, "device": device}
        if trace:
            ctx = trace_reduce.Context(
                trace=trace_reduce.load(newest_xplane(trace_dir)),
                chips=chips, steps=steps, dispatch_s=dispatch_s, job=job,
                peaks=peaks)
            result["metrics"] = read_layer_metrics(spec["per_layer"], ctx)
            busy = ctx.busy_and_window_s()
            if busy:
                device["busy_s"], device["window_s"] = busy
                result["breakdown"] = ctx.breakdown()
        else:
            values = {"samples_per_s_chip": rate,
                      "step_mem_GiB": step_bytes / 2 ** 30,
                      "setup_s": setup_s}
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in spec["end_to_end"]}
        return result
    finally:
        job.close()


def main(argv, t_start):
    import argparse

    parser = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    print(json.dumps(result), flush=True)
    return 0
