"""Set-up is measured from inside the program (docs/diagnostics.md,
"Set-up spans"): ``common/metrics.py`` records spans beside its counters,
on the profiler's clock; the program opens one at each boundary a job
crosses before its first step; jax's compile phases are taken in as
children; the kernels count the ``pallas_call``s the host traces. Nothing
of it enters a compiled program."""
import json
import os
import re
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.common import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_MODULES = ("jit_hvd_dp_step", "jit_hvd_decoder_step",
                "jit_hvd_decoder_bias_step", "jit_hvd_zero_step")


@pytest.fixture(autouse=True)
def fresh_spans():
    """A worker that ran other files first may have filled the list."""
    metrics.reset()
    yield
    metrics.reset()


def _by_name(records=None):
    out = {}
    for r in metrics.spans() if records is None else records:
        out.setdefault(r["name"], []).append(r)
    return out


def _run(script, tmp_path, timeout=240, **env):
    """A process of its own, on the CPU, with a time limit of its own;
    the last line of its output is JSON."""
    from conftest import subprocess_cpu_env

    path = tmp_path / "script.py"
    path.write_text(textwrap.dedent(script))
    done = subprocess.run(
        [sys.executable, str(path)], cwd=ROOT, timeout=timeout,
        env=subprocess_cpu_env(PYTHONPATH=ROOT, **env),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


# ---- the record ------------------------------------------------------------


def test_a_span_nests_under_the_innermost_open_span_of_its_thread():
    seen = {}

    def other():
        with metrics.span("t.outer") as outer:
            with metrics.span("t.inner"):
                pass
        seen["thread"] = outer

    with metrics.span("m.outer"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join()
        with metrics.span("m.inner"):
            with metrics.span("m.innermost"):
                pass
        with metrics.span("m.second"):
            pass
    with metrics.span("m.root"):
        pass

    by = {name: rows[0] for name, rows in _by_name().items()}
    assert [r["id"] for r in metrics.spans()] == list(range(7))
    assert by["m.outer"]["parent"] is None
    assert by["m.root"]["parent"] is None
    assert by["m.inner"]["parent"] == by["m.outer"]["id"]
    assert by["m.innermost"]["parent"] == by["m.inner"]["id"]
    assert by["m.second"]["parent"] == by["m.outer"]["id"]
    # The other thread's spans are roots of their own, whatever this
    # thread had open.
    assert by["t.outer"]["parent"] is None
    assert by["t.inner"]["parent"] == by["t.outer"]["id"]
    assert by["t.outer"]["thread"] != by["m.outer"]["thread"]
    assert by["t.inner"]["thread"] == by["t.outer"]["thread"]
    for r in metrics.spans():
        assert r["start_ns"] <= r["end_ns"]
        assert abs(r["end_ns"] - time.time_ns()) < 60e9  # time.time_ns()


def test_self_time_is_the_duration_less_what_the_children_cover():
    with metrics.span("outer"):
        time.sleep(0.02)
        with metrics.span("child"):
            time.sleep(0.03)
            with metrics.span("grandchild"):
                time.sleep(0.01)
        with metrics.span("child"):
            time.sleep(0.02)
    by = _by_name()
    (outer,), children = by["outer"], by["child"]
    (grandchild,) = by["grandchild"]
    length = lambda r: r["end_ns"] - r["start_ns"]
    assert outer["self_ns"] == length(outer) - sum(map(length, children))
    assert children[0]["self_ns"] == length(children[0]) - length(grandchild)
    assert children[1]["self_ns"] == length(children[1])
    assert grandchild["self_ns"] == length(grandchild)
    assert outer["self_ns"] >= 0.02e9
    # Disjoint self times add up to the time the tree covers.
    assert sum(r["self_ns"] for r in metrics.spans()) == length(outer)


def test_a_span_still_open_ends_now():
    with metrics.span("open"):
        (seen,) = metrics.spans()
        assert seen["end_ns"] >= seen["start_ns"]
        assert seen["self_ns"] == seen["end_ns"] - seen["start_ns"]


def test_counts_may_be_added_until_the_span_closes():
    with metrics.span("load", built=0, leaves=3) as load:
        load.add(built=True, bytes=4096)
        load.add(bytes=4)
    (seen,) = metrics.spans()
    assert seen["counts"] == {"built": 1, "leaves": 3, "bytes": 4100}
    # spans() hands out copies.
    seen["counts"]["built"] = 7
    assert metrics.spans()[0]["counts"]["built"] == 1


def test_the_decorator_records_every_call():
    @metrics.span("step.build")
    def build(x, y=1):
        """doc"""
        return x + y

    assert build(1) == 2 and build(1, y=2) == 3
    assert build.__name__ == "build" and build.__doc__ == "doc"
    assert [r["name"] for r in metrics.spans()] == ["step.build"] * 2


def test_the_cap_refuses_and_counts(monkeypatch):
    monkeypatch.setattr(metrics, "SPAN_CAP", 3)
    for i in range(5):
        with metrics.span(f"s{i}"):
            with metrics.span("inner"):
                pass
    assert len(metrics.spans()) == 3
    assert metrics.spans_refused() == 7
    assert "spans refused past 3: 7" in metrics.report_text()
    # A refused span leaves nothing open behind it.
    monkeypatch.setattr(metrics, "SPAN_CAP", 4)
    with metrics.span("later"):
        pass
    assert metrics.spans()[-1]["name"] == "later"
    assert metrics.spans()[-1]["parent"] is None


def test_tree_counts_of_arrays_and_of_tracers():
    tree = {"a": jnp.zeros((3, 4), jnp.float32), "b": [jnp.zeros(5, jnp.int8)],
            "c": None}
    assert metrics.tree_counts(tree) == {"leaves": 2, "bytes": 53}
    seen = []
    jax.make_jaxpr(lambda t: seen.append(metrics.tree_counts(t)) or t)(tree)
    assert seen == [{"leaves": 2, "bytes": 53}]


# ---- jax's phases ----------------------------------------------------------


def test_jax_phases_hang_under_the_span_that_holds_them():
    """A tiny program named as a step module, lowered and compiled ahead
    of time inside an open span: trace, lowering and compile are its
    children under the module's name; a span opened while jax traces is
    the trace's, and the trace of a ``jit`` called inside the step stays
    in the time of the trace it is part of."""
    inner = jax.jit(lambda x: x * 2)

    def hvd_decoder_step(x):
        with metrics.span("probe.inside"):  # opened while jax traces
            y = inner(x)
        return y + 1

    with metrics.span("probe.before"):
        pass
    with metrics.span("probe") as probe:
        lowered = jax.jit(hvd_decoder_step).lower(
            jax.ShapeDtypeStruct((3,), jnp.float32))
        lowered.compile()
    by = _by_name()
    module = "jit_hvd_decoder_step"
    assert module in STEP_MODULES
    (outer,) = by["probe"]
    phases = [by[f"{phase}:{module}"] for phase in
              ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")]
    assert all(len(rows) == 1 for rows in phases)
    trace, lower, load = (rows[0] for rows in phases)
    assert {trace["parent"], lower["parent"], load["parent"]} == {outer["id"]}
    assert trace["end_ns"] <= lower["start_ns"] <= lower["end_ns"]
    assert lower["end_ns"] <= load["start_ns"] <= load["end_ns"]
    assert outer["start_ns"] <= trace["start_ns"]
    assert load["end_ns"] <= outer["end_ns"]
    (inside,) = by["probe.inside"]
    assert inside["parent"] == trace["id"]
    assert not any(name.startswith("jaxpr_trace:") and name != trace["name"]
                   for name in by)
    assert by["probe.before"][0]["parent"] is None
    # Disjoint: the probe's time is its own and its descendants' selves.
    family = [r for r in metrics.spans()
              if r["id"] != by["probe.before"][0]["id"]]
    assert abs(sum(r["self_ns"] for r in family)
               - (outer["end_ns"] - outer["start_ns"])) < 10_000
    assert probe.counts == {}


_CACHED_COMPILE = """
    import json, sys
    import jax, jax.numpy as jnp
    from horovod_tpu.common import metrics

    jax.config.update("jax_compilation_cache_dir", sys.argv[0] + ".cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def hvd_dp_step(x):
        return jnp.tanh(x) @ x

    with metrics.span("probe"):
        jax.jit(hvd_dp_step).lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    print(json.dumps(metrics.spans()))
"""


def test_a_compile_read_from_the_cache_says_so(tmp_path):
    """Two fresh processes against one persistent cache directory: the
    first compiles (``cache_hit`` 0), the second reads the executable
    back (``cache_hit`` 1, with the time the read took)."""
    hits = []
    for _ in range(2):
        by = _by_name(_run(_CACHED_COMPILE, tmp_path))
        (probe,), (load,) = by["probe"], by["backend_compile:jit_hvd_dp_step"]
        assert load["parent"] == probe["id"]
        hits.append(load["counts"])
    assert hits[0] == {"cache_hit": 0}
    assert hits[1]["cache_hit"] == 1 and hits[1]["retrieval_ms"] >= 0


# ---- the clock -------------------------------------------------------------

_PROFILED_SPAN = """
    import glob, json, sys, time
    import jax, jax.numpy as jnp
    from horovod_tpu.common import metrics

    where = sys.argv[0] + ".trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    with metrics.span("probe.clock"):
        time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(where + "/plugins/profile/*/*.xplane.pb")
    planes = {p.name: p for p in
              jax.profiler.ProfileData.from_file(path).planes}
    began = dict(planes["Task Environment"].stats)["profile_start_time"]
    found = [(began + e.start_ns, e.duration_ns)
             for line in planes["/host:CPU"].lines for e in line.events
             if e.name == "probe.clock"]
    (span,) = [r for r in metrics.spans() if r["name"] == "probe.clock"]
    print(json.dumps({"found": found, "span": span}))
"""


def test_a_span_is_on_the_host_lines_of_a_profile_on_the_same_clock(tmp_path):
    """Under ``jax.profiler.start_trace`` the span is an event of its own
    name on ``/host:CPU``, and the time the trace gives it (the file's
    ``profile_start_time`` plus the event's offset) is the recorded
    ``start_ns`` within a millisecond: one clock."""
    out = _run(_PROFILED_SPAN, tmp_path)
    ((start_ns, duration_ns),) = out["found"]
    span = out["span"]
    assert abs(start_ns - span["start_ns"]) < 1e6
    assert abs(duration_ns - (span["end_ns"] - span["start_ns"])) < 1e6


# ---- where the program opens them ------------------------------------------

_INIT_AND_SHUTDOWN = """
    import json
    import horovod_tpu as hvd

    hvd.init()
    spans, report = hvd.metrics()["spans"], hvd.metrics_report()
    hvd.shutdown()
    print(json.dumps({"spans": spans, "report": report,
                      "after": [r["name"] for r in hvd.metrics()["spans"]]}))
"""


def test_init_leaves_its_spans_in_the_metrics_and_the_report(tmp_path):
    out = _run(_INIT_AND_SHUTDOWN, tmp_path)
    by = _by_name(out["spans"])
    for name in ("import:horovod_tpu", "init", "mesh", "native.load",
                 "native.make_q", "engine.start"):
        assert len(by[name]) == 1, name
    (imported,), (init,) = by["import:horovod_tpu"], by["init"]
    assert imported["parent"] is None and init["parent"] is None
    assert imported["end_ns"] <= init["start_ns"]
    assert init["counts"] == {"size": 1}  # a process of one CPU device
    assert by["mesh"][0]["counts"] == {"devices": 1}
    assert by["mesh"][0]["parent"] == init["id"]
    (engine,), (load,) = by["engine.start"], by["native.load"]
    assert engine["parent"] == init["id"]
    assert load["parent"] == engine["id"]
    assert load["counts"]["built"] in (0, 1)
    assert by["native.make_q"][0]["parent"] == load["id"]
    assert ("native.build" in by) == bool(load["counts"]["built"])
    for name in by:
        if ":" not in name:
            assert re.search(rf"^{re.escape(name)}: n=1 total=[\d.]+ "
                             rf"self=[\d.]+$", out["report"], re.M), name
    assert "-- spans (ms) --" in out["report"]
    # The record outlives the world it describes.
    assert set(by) <= set(out["after"])


def _tiny_decoder():
    from horovod_tpu.models.transformer import (
        TransformerConfig, init_params, make_train_step, shard_params)
    from horovod_tpu.parallel.mesh import build_parallel_mesh
    from horovod_tpu.training import init_opt_state

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, d_head=16,
                            d_ff=64, n_layers=2, max_seq=16)
    mesh = build_parallel_mesh(jax.devices()[:2], sp=1, tp=1, pp=1)
    opt = optax.adamw(1e-3)
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1), cfg,
                          mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    step = make_train_step(cfg, opt, mesh, n_microbatches=1)
    return step, (params, init_opt_state(opt, params, mesh), tokens,
                  jnp.roll(tokens, -1, axis=1))


def test_a_decoder_job_opens_its_spans_and_its_step_reports_its_phases():
    import horovod_tpu.models.transformer  # noqa: F401 (before the reset)

    metrics.reset()
    step, args = _tiny_decoder()
    step.lower(*args).compile()
    by = _by_name()
    assert by["mesh"][0]["counts"] == {"devices": 2}
    params, opt_state = args[:2]
    leaves = len(jax.tree_util.tree_leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    assert by["state.shard"][0]["counts"] == {"leaves": leaves,
                                              "bytes": nbytes}
    assert by["state.opt"][0]["counts"]["leaves"] == 2 * leaves + 1
    assert by["state.opt"][0]["counts"]["bytes"] == 2 * nbytes + 4
    assert len(by["step.build"]) == 1
    for phase in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"):
        (row,) = by[f"{phase}:jit_hvd_decoder_step"]
        assert row["parent"] is None


def test_the_data_parallel_path_opens_its_spans(hvd):
    import flax.linen as nn

    from horovod_tpu.training import (
        init_train_state, make_train_step, replicate_state)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    model, opt = Net(), optax.sgd(0.1, momentum=0.9)
    sample = jnp.zeros((1, 2, 2, 1), jnp.float32)
    # As the benchmark's runner does: the initialiser under jit, so the
    # span opens while jax traces and ends up inside that trace.
    state = replicate_state(
        jax.jit(lambda k: init_train_state(model, opt, k, sample))(
            jax.random.PRNGKey(0)), hvd.mesh())
    make_train_step(model, opt, hvd.mesh())
    by = _by_name()
    counts = metrics.tree_counts(state)
    assert counts["leaves"] == 5  # kernel, bias, their momenta, the step
    assert by["state.init"][0]["counts"] == counts
    assert by["state.replicate"][0]["counts"] == counts
    assert len(by["step.build"]) == 1
    holder = metrics.spans()[by["state.init"][0]["parent"]]
    assert holder["name"] == "jaxpr_trace:jit__lambda"
    assert holder["parent"] is None


def test_import_spans_exist_for_the_packages_that_take_time():
    """Read in a process that imports them anew: this one reset the
    record after importing."""
    done = subprocess.run(
        [sys.executable, "-c",
         "import json, horovod_tpu.models.transformer as t;"
         "from horovod_tpu.common import metrics;"
         "print(json.dumps(metrics.spans()))"],
        cwd=ROOT, timeout=240, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    by = _by_name(json.loads(done.stdout.strip().splitlines()[-1]))
    root, models, decoder, scan = (
        by[f"import:horovod_tpu{rest}"][0]
        for rest in ("", ".models", ".models.transformer", ".ops.ssd"))
    assert root["parent"] is None and models["parent"] is None
    assert root["end_ns"] <= models["start_ns"] <= decoder["start_ns"]
    assert scan["parent"] == decoder["id"]


# ---- the kernels count what the host traces --------------------------------


@pytest.mark.parametrize("kernel, counters", [
    ("forward", {"kernels.traced.flash_fwd": 1}),
    ("backward", {"kernels.traced.flash_bwd": 1}),
    # A sequence too long for the fused kernel (here: no budget for it).
    ("two-pass backward", {"kernels.traced.flash_dq": 1,
                           "kernels.traced.flash_dkv": 1}),
])
def test_the_flash_kernels_count_their_traced_calls(monkeypatch, kernel,
                                                    counters):
    from horovod_tpu.ops import pallas_attention as pa

    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    q = jax.ShapeDtypeStruct((2, 16, 8), jnp.float32)
    row = jax.ShapeDtypeStruct((2, 16, 1), jnp.float32)
    offs = jax.ShapeDtypeStruct((2,), jnp.int32)
    if kernel == "forward":
        jaxpr = jax.make_jaxpr(lambda q, o: pa._flash_forward(
            q, q, q, o, True, True, "train"))(q, offs)
    else:
        if kernel == "two-pass backward":
            monkeypatch.setattr(pa, "BWD_VMEM_BUDGET", 0)
        jaxpr = jax.make_jaxpr(lambda q, r, o: pa._pallas_bwd(
            q, q, q, q, r, r, o, True, True))(q, row, offs)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert metrics.counters() == counters
    assert sum(counters.values()) == len(calls)


def test_the_scan_kernels_count_their_traced_calls(monkeypatch):
    from horovod_tpu.ops import ssd

    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    b, T, H, P, N, Q = 1, 256, 8, 64, 128, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, T, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(key, (b, T, H)))
    A = -jnp.ones((H,), jnp.float32)
    B = jax.random.normal(key, (b, T, N), jnp.float32)
    D = jnp.ones((H,), jnp.float32)

    def loss(x):
        return ssd.ssd_chunked(x, dt, A, B, B, D, chunk=Q).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(x)
    assert "pallas_call" in str(jaxpr)
    assert metrics.counters() == {"kernels.traced.ssd_fwd": 1,
                                  "kernels.traced.ssd_bwd": 1}


def test_the_grouped_matmuls_count_their_traced_calls(monkeypatch):
    from horovod_tpu.parallel import moe

    monkeypatch.setenv("HVD_PALLAS_INTERPRET", "1")
    lhs = jnp.ones((256, 128), jnp.float32)
    rhs = jnp.ones((2, 128, 128), jnp.float32)
    sizes = jnp.array([128, 128], jnp.int32)

    def loss(lhs, rhs):
        return moe._grouped_matmul(lhs, rhs, sizes).sum()

    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(lhs, rhs)
    # The product, and for the gradients its transpose and one tgmm.
    assert metrics.counters() == {"kernels.traced.gmm": 2,
                                  "kernels.traced.tgmm": 1}


# ---- nothing enters a compiled program -------------------------------------


def _documented_scopes():
    """The names in the first column of the scope table of
    docs/diagnostics.md, "Tracing"."""
    with open(os.path.join(ROOT, "docs", "diagnostics.md")) as f:
        tracing = f.read().split("\n## Tracing\n")[1]
    table = tracing.split("| Scope | Put by | Covers |")[1].split("\n\n")[0]
    names = set()
    for row in table.splitlines()[2:]:
        names.update(re.findall(r"`([^`]+)`", row.split("|")[1]))
    return names


def _scopes_of(jaxpr, out):
    from jax._src import core, source_info_util

    for eqn in jaxpr.eqns:
        out.update(el.name for el in eqn.source_info.name_stack.stack
                   if isinstance(el, source_info_util.Scope))
        for sub in core.jaxprs_in_params(eqn.params):
            _scopes_of(sub, out)
    return out


def test_the_step_holds_no_scope_the_documentation_does_not_list():
    """Set-up spans are the host's: the traced step carries the scopes of
    docs/diagnostics.md's table (and an einsum's own, its subscripts) and
    nothing else, and its lowered module has the documented name."""
    documented = _documented_scopes()
    assert {"forward", "optimizer", "attention", "flash_fwd"} <= documented
    step, args = _tiny_decoder()
    scopes = {s for s in _scopes_of(jax.make_jaxpr(step)(*args).jaxpr, set())
              if "->" not in s}
    assert scopes and scopes <= documented, scopes - documented
    text = step.lower(*args).as_text(debug_info=True)
    assert "module @jit_hvd_decoder_step " in text
    for name in metrics.spans():
        assert f'/{name["name"]}/' not in text
