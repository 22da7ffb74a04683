"""Native core runtime tests: library load, engine integration, and a real
2-process TCP controller + ring data-plane run (the reference's
mpirun-launched Pattern-1 tests, SURVEY §4, done with subprocesses)."""

import os
import textwrap

import numpy as np
import pytest

from horovod_tpu.common import native as hn

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)


def _run_workers(tmp_path, script_text, sentinel, size=2, timeout=120,
                 extra_args=()):
    """Launch `size` worker subprocesses of `script_text` (argv: rank,
    [extra_args...,] port) and assert each exits 0 printing
    `{sentinel}_{rank}_OK`."""
    from proc_harness import run_world

    run_world(tmp_path, script_text, sentinel, size=size, timeout=timeout,
              args_for_rank=lambda rank, port: [*extra_args, port])


def test_library_loads():
    assert hn.load_library() is not None


_SLOW_MAKEFILE = """\
../lib/libhvdtpu.so: src.txt
\t@mkdir -p ../lib
\tprintf half > $@; sleep 1; printf whole > $@
"""


def test_concurrent_builds_on_an_empty_lib_wait_for_the_whole_library(
        tmp_path):
    """Two ranks start on a tree with no lib/: the one that loses the race
    must not take the winner's half-linked library for an up-to-date one
    (``make -q`` would say so). This recipe writes in place, as a linker
    does, so only the lock around check AND build makes both see the
    whole file."""
    import subprocess
    import sys

    (tmp_path / "csrc").mkdir()
    (tmp_path / "csrc" / "Makefile").write_text(_SLOW_MAKEFILE)
    (tmp_path / "csrc" / "src.txt").write_text("source")
    script = textwrap.dedent(f"""
        import sys, time
        from horovod_tpu.common import native as hn
        hn._CSRC_DIR = {str(tmp_path / "csrc")!r}
        hn._LIB_DIR = {str(tmp_path / "lib")!r}
        time.sleep(float(sys.argv[1]))
        hn._build_library()
        print(open(hn._lib_path()).read())
    """)
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("HOROVOD_NATIVE_SANITIZE", None)
    procs = [subprocess.Popen([sys.executable, "-c", script, delay], env=env,
                              stdout=subprocess.PIPE, text=True)
             for delay in ("0", "0.4")]
    assert [p.communicate(timeout=60)[0].strip() for p in procs] == \
        ["whole", "whole"]
    assert [p.returncode for p in procs] == [0, 0]


def test_no_make_loads_the_shipped_library_or_says_why(tmp_path, monkeypatch):
    """A wheel ships lib/*.so; a machine without ``make`` loads it as it
    is. With neither, the error names what is missing."""
    monkeypatch.setenv("PATH", str(tmp_path))  # no make here
    hn._build_library()  # the tree's library is there: nothing to do
    monkeypatch.setattr(hn, "_LIB_DIR", str(tmp_path / "lib"))
    with pytest.raises(RuntimeError, match="cannot be built"):
        hn._build_library()


def test_engine_uses_native_core(hvd):
    from horovod_tpu.common.state import global_state

    assert global_state().engine._native, (
        "eager engine should run on the native control plane")


def test_many_async_submissions_one_cycle(hvd):
    # Submissions landing within one 5 ms cycle get fused by the native
    # controller; all must resolve correctly regardless of binning.
    n = hvd.size()
    handles = []
    for i in range(12):
        xs = [np.full((32,), r * (i + 1), np.float32) for r in range(n)]
        handles.append(hvd.allreduce_async(xs, name=f"fuse.{i}", op=hvd.Sum))
    for i, h in enumerate(handles):
        out = hvd.synchronize(h)
        expected = sum(range(n)) * (i + 1)
        np.testing.assert_allclose(np.asarray(out[0]), expected)


def test_native_duplicate_name(hvd):
    from horovod_tpu.common.exceptions import DuplicateTensorNameError

    xs = [np.ones((4,), np.float32) for _ in range(hvd.size())]
    h = hvd.allreduce_async(xs, name="ndup")
    with pytest.raises(DuplicateTensorNameError):
        hvd.allreduce_async(xs, name="ndup")
    hvd.synchronize(h)


def test_mixed_ops_in_flight(hvd):
    n = hvd.size()
    a = hvd.allreduce_async(
        [np.full((8,), r, np.float32) for r in range(n)], name="m.ar",
        op=hvd.Sum)
    b = hvd.broadcast_async(
        [np.full((8,), r, np.float32) for r in range(n)], 2, name="m.bc")
    c = hvd.allgather_async(
        [np.full((2, 3), r, np.float32) for r in range(n)], name="m.ag")
    np.testing.assert_allclose(np.asarray(hvd.synchronize(a)[0]),
                               sum(range(n)))
    np.testing.assert_allclose(np.asarray(hvd.synchronize(b)[0]), 2)
    assert np.asarray(hvd.synchronize(c)).shape == (2 * n, 3)


_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); size = int(sys.argv[2])
    port = int(sys.argv[3])
    core = hn.NativeCore()
    assert core.available
    ok = core.init(rank=rank, size=size, local_rank=0, local_size=1,
                   cross_rank=rank, cross_size=size,
                   coordinator_addr="127.0.0.1", coordinator_port=port,
                   my_host="127.0.0.1", cycle_time_ms=1.0,
                   fusion_threshold=64 << 20, cache_capacity=64,
                   stall_warning_sec=60.0, stall_shutdown_sec=0.0,
                   stall_check_enabled=True,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "no xla executor in this test"))
    assert ok, "native init failed"

    # host-plane fused allreduce (two tensors, same dtype -> one response)
    a = np.full(1000, float(rank + 1), np.float32)
    b = np.arange(100, dtype=np.float32) * (rank + 1)
    ha = core.enqueue("t.a", hn.OP_ALLREDUCE, 1, 7, a.shape,
                      data_ptr=a.ctypes.data, output_ptr=a.ctypes.data,
                      plane=hn.PLANE_HOST)
    hb = core.enqueue("t.b", hn.OP_ALLREDUCE, 1, 7, b.shape,
                      data_ptr=b.ctypes.data, output_ptr=b.ctypes.data,
                      plane=hn.PLANE_HOST)
    r, err = core.wait(ha); assert r == 1, err
    r, err = core.wait(hb); assert r == 1, err
    expect_a = sum(range(1, size + 1))
    assert np.allclose(a, expect_a), a[:4]
    assert np.allclose(b, np.arange(100) * sum(range(1, size + 1))), b[:4]

    # broadcast from rank 1
    c = np.full(17, float(rank * 10), np.float64)
    hc = core.enqueue("t.c", hn.OP_BROADCAST, 1, 8, c.shape,
                      data_ptr=c.ctypes.data, output_ptr=c.ctypes.data,
                      root_rank=1, plane=hn.PLANE_HOST)
    r, err = core.wait(hc); assert r == 1, err
    assert np.allclose(c, 10.0), c[:4]

    # allgather (equal shapes)
    d = np.full(5, float(rank), np.float32)
    out = np.zeros(5 * size, np.float32)
    hd = core.enqueue("t.d", hn.OP_ALLGATHER, 1, 7, d.shape,
                      data_ptr=d.ctypes.data, output_ptr=out.ctypes.data,
                      plane=hn.PLANE_HOST)
    r, err = core.wait(hd); assert r == 1, err
    for rr in range(size):
        assert np.allclose(out[rr * 5:(rr + 1) * 5], rr), out

    # adasum (power-of-two world): compare against the pairwise-recursion
    # oracle computed from the known per-rank inputs.
    e = np.array([1.0, 2.0, 3.0], np.float32) * (rank + 1)
    he = core.enqueue("t.e", hn.OP_ALLREDUCE, 2, 7, e.shape,
                      data_ptr=e.ctypes.data, output_ptr=e.ctypes.data,
                      plane=hn.PLANE_HOST)
    r, err = core.wait(he); assert r == 1, err
    from horovod_tpu.ops.adasum import adasum_reference
    expected_e = adasum_reference(
        [np.array([1.0, 2.0, 3.0]) * (rr + 1) for rr in range(size)])
    assert np.allclose(e, expected_e, rtol=1e-4), (e, expected_e)

    # bf16 allreduce with fp32 accumulation (dtype code 10)
    f32 = np.full(8, 1.0 + 2 ** -9, np.float32)
    bf = ((f32.view(np.uint32) + 0x7FFF + ((f32.view(np.uint32) >> 16) & 1))
          >> 16).astype(np.uint16)
    hf = core.enqueue("t.f", hn.OP_ALLREDUCE, 1, 10, bf.shape,
                      data_ptr=bf.ctypes.data, output_ptr=bf.ctypes.data,
                      plane=hn.PLANE_HOST)
    r, err = core.wait(hf); assert r == 1, err
    back = (bf.astype(np.uint32) << 16).view(np.float32)
    assert np.allclose(back, size * (1.0 + 2 ** -9), rtol=1e-2), back

    # dtype-mismatch across ranks -> coordinator validation error
    g = (np.full(4, 1.0, np.float32) if rank == 0
         else np.full(4, 1.0, np.float64))
    hg = core.enqueue("t.g", hn.OP_ALLREDUCE, 1, 7 if rank == 0 else 8,
                      g.shape, data_ptr=g.ctypes.data,
                      output_ptr=g.ctypes.data, plane=hn.PLANE_HOST)
    r, err = core.wait(hg)
    assert r == -1 and "Mismatched data types" in err, (r, err)

    core.shutdown()
    print(f"WORKER_{rank}_OK")
""")


@pytest.mark.parametrize("size", [2, 4])
def test_multiprocess_tcp_controller_and_ring(size, tmp_path):
    _run_workers(tmp_path, _WORKER, "WORKER", size=size,
                 extra_args=(size,))


_ADASUM_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); size = int(sys.argv[2])
    port = int(sys.argv[3])
    core = hn.NativeCore()
    assert core.available
    ok = core.init(rank=rank, size=size, local_rank=0, local_size=1,
                   cross_rank=rank, cross_size=size,
                   coordinator_addr="127.0.0.1", coordinator_port=port,
                   my_host="127.0.0.1", cycle_time_ms=1.0,
                   fusion_threshold=64 << 20, cache_capacity=64,
                   stall_warning_sec=60.0, stall_shutdown_sec=0.0,
                   stall_check_enabled=True,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "no xla executor in this test"))
    assert ok, "native init failed"

    from horovod_tpu.ops.adasum import adasum_reference

    def run_adasum(name, arr):
        h = core.enqueue(name, hn.OP_ALLREDUCE, 2, 7, arr.shape,
                         data_ptr=arr.ctypes.data,
                         output_ptr=arr.ctypes.data, plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
        return arr

    # 1) Two same-dtype Adasum tensors submitted together fuse into one
    #    response; the combination must be applied PER TENSOR (reference
    #    tensor_counts contract) — a joint-buffer combination gives
    #    different numbers for non-parallel inputs like these.
    def va(r):
        return (np.arange(5, dtype=np.float32) + 1.0) * (r + 1)
    def vb(r):
        v = np.zeros(7, np.float32)
        v[r % 7] = 3.0 + r
        v[(r + 2) % 7] = 1.0
        return v
    a = va(rank); b = vb(rank)
    ha = core.enqueue("ad.a", hn.OP_ALLREDUCE, 2, 7, a.shape,
                      data_ptr=a.ctypes.data, output_ptr=a.ctypes.data,
                      plane=hn.PLANE_HOST)
    hb = core.enqueue("ad.b", hn.OP_ALLREDUCE, 2, 7, b.shape,
                      data_ptr=b.ctypes.data, output_ptr=b.ctypes.data,
                      plane=hn.PLANE_HOST)
    r, err = core.wait(ha); assert r == 1, err
    r, err = core.wait(hb); assert r == 1, err
    ea = adasum_reference([va(rr) for rr in range(size)])
    eb = adasum_reference([vb(rr) for rr in range(size)])
    assert np.allclose(a, ea, rtol=1e-4), (a, ea)
    assert np.allclose(b, eb, rtol=1e-4), (b, eb)

    # 2) Odd length (uneven halving at every VHDD level) + length shorter
    #    than the world (empty fragments on some ranks).
    for n_elem in (13, max(1, size - 1)):
        c = np.cos(np.arange(n_elem) * (rank + 1)).astype(np.float32)
        run_adasum(f"ad.odd{n_elem}", c)
        ec = adasum_reference(
            [np.cos(np.arange(n_elem) * (rr + 1)) for rr in range(size)])
        assert np.allclose(c, ec, rtol=1e-4), (n_elem, c, ec)

    # 3) bf16 Adasum through the VHDD path: fp32 accumulation with
    #    bf16 storage between levels (loose tolerance — bf16 has ~3
    #    decimal digits).
    def to_bf16(v32):
        u = v32.astype(np.float32).view(np.uint32)
        return ((u + 0x7FFF + ((u >> 16) & 1)) >> 16).astype(np.uint16)

    def from_bf16(u16):
        return (u16.astype(np.uint32) << 16).view(np.float32)

    vb16 = (np.linspace(0.25, 2.0, 12).astype(np.float32)
            * (1.0 + 0.1 * rank))
    buf16 = to_bf16(vb16)
    hb16 = core.enqueue("ad.bf16", hn.OP_ALLREDUCE, 2, 10, buf16.shape,
                        data_ptr=buf16.ctypes.data,
                        output_ptr=buf16.ctypes.data, plane=hn.PLANE_HOST)
    r, err = core.wait(hb16); assert r == 1, err
    eb16 = adasum_reference(
        [from_bf16(to_bf16(np.linspace(0.25, 2.0, 12).astype(np.float32)
                           * (1.0 + 0.1 * rr)))
         for rr in range(size)])
    assert np.allclose(from_bf16(buf16), eb16, rtol=3e-2), (
        from_bf16(buf16), eb16)

    # 4) Wire-traffic complexity: VHDD must be O(count) per rank. The
    #    halving leg sends < count floats, the allgather leg < count
    #    more, scalars are negligible -> well under 3*count*4 bytes.
    #    The old allgather-everything scheme sent (size-1)*count*4.
    count = 1 << 16
    before = core.ring_bytes_sent()
    d = np.sin(np.arange(count) + rank).astype(np.float32)
    run_adasum("ad.big", d)
    delta = core.ring_bytes_sent() - before
    limit = 3 * count * 4
    assert delta < limit, (delta, limit)
    ed = adasum_reference(
        [np.sin(np.arange(count) + rr) for rr in range(size)])
    assert np.allclose(d, ed, rtol=1e-3, atol=1e-5)

    # 5) 16-bit floats ride the wire at 16-BIT width (the reference's
    #    fp16-on-wire AVX path): the same vector as bf16 must move under
    #    3*count*2 bytes — half the fp32 bound.
    before = core.ring_bytes_sent()
    d16 = to_bf16(np.sin(np.arange(count) + rank).astype(np.float32))
    h16 = core.enqueue("ad.big16", hn.OP_ALLREDUCE, 2, 10, d16.shape,
                       data_ptr=d16.ctypes.data,
                       output_ptr=d16.ctypes.data, plane=hn.PLANE_HOST)
    r, err = core.wait(h16); assert r == 1, err
    delta16 = core.ring_bytes_sent() - before
    assert delta16 < 3 * count * 2, (delta16, 3 * count * 2)
    # Oracle has no intermediate rounding; the wire path rounds to bf16
    # at every level (eps ~0.8%), so the bound is log2(size) roundings
    # of O(1) values.
    e16 = adasum_reference(
        [from_bf16(to_bf16(np.sin(np.arange(count) + rr)
                           .astype(np.float32))) for rr in range(size)])
    assert np.allclose(from_bf16(d16), e16, rtol=5e-2, atol=3e-2)

    core.shutdown()
    print(f"ADASUM_{rank}_OK")
""")


@pytest.mark.parametrize("size", [4, 8])
def test_adasum_vhdd_multiprocess(size, tmp_path):
    """True-VHDD host-plane Adasum: per-tensor fused semantics, uneven
    halving, empty fragments, and the O(count) per-rank traffic bound
    (reference adasum.h:194-398; VERDICT r4 'What's missing' #3/#4)."""
    _run_workers(tmp_path, _ADASUM_WORKER, "ADASUM", size=size,
                 extra_args=(size,))


_ADASUM_FUZZ_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); size = int(sys.argv[2])
    port = int(sys.argv[3])
    core = hn.NativeCore()
    assert core.available
    ok = core.init(rank=rank, size=size, local_rank=0, local_size=1,
                   cross_rank=rank, cross_size=size,
                   coordinator_addr="127.0.0.1", coordinator_port=port,
                   my_host="127.0.0.1", cycle_time_ms=1.0,
                   fusion_threshold=64 << 20, cache_capacity=256,
                   stall_warning_sec=60.0, stall_shutdown_sec=0.0,
                   stall_check_enabled=True,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "host-plane only"))
    assert ok, "native init failed"

    from horovod_tpu.ops.adasum import adasum_reference

    # Deterministic random layouts, identical on every rank: rounds of
    # K tensors with adversarial lengths (1, primes, pow2 +- 1) fused by
    # the controller however the cycle timing bins them — per-tensor
    # VHDD bookkeeping must hold for every layout.
    layout_rng = np.random.RandomState(1234)
    for rnd in range(6):
        k = int(layout_rng.randint(1, 6))
        lens = [int(layout_rng.choice([1, 2, 3, 7, 13, 31, 64, 65, 127]))
                for _ in range(k)]
        bufs = []
        for t, n in enumerate(lens):
            v = (np.cos(np.arange(n) * (0.37 + t) + rank * 1.7)
                 .astype(np.float32) * (1.0 + 0.2 * rank))
            bufs.append(v)
        handles = [
            core.enqueue(f"fz.{rnd}.{t}", hn.OP_ALLREDUCE, 2, 7,
                         b.shape, data_ptr=b.ctypes.data,
                         output_ptr=b.ctypes.data, plane=hn.PLANE_HOST)
            for t, b in enumerate(bufs)
        ]
        for h in handles:
            r, err = core.wait(h); assert r == 1, err
        for t, (n, b) in enumerate(zip(lens, bufs)):
            expect = adasum_reference(
                [np.cos(np.arange(n) * (0.37 + t) + rr * 1.7)
                 * (1.0 + 0.2 * rr) for rr in range(size)])
            assert np.allclose(b, expect, rtol=1e-4, atol=1e-6), (
                rnd, t, n, b, expect)

    core.shutdown()
    print(f"ADFUZZ_{rank}_OK")
""")


@pytest.mark.full
def test_adasum_fused_layout_fuzz(tmp_path):
    """Randomized multi-tensor Adasum layouts at 4 ranks: whatever the
    cycle fuses together, per-tensor VHDD bookkeeping (SplitCounts +
    segment scalars) must match the per-tensor oracle for adversarial
    lengths (1, primes, pow2 +- 1) — the trickiest code added this
    round, soak-tested."""
    _run_workers(tmp_path, _ADASUM_FUZZ_WORKER, "ADFUZZ", size=4,
                 extra_args=(4,), timeout=300)


_STALL_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.available
    ok = core.init(rank=rank, size=2, local_rank=0, local_size=1,
                   cross_rank=rank, cross_size=2,
                   coordinator_addr="127.0.0.1", coordinator_port=port,
                   my_host="127.0.0.1", cycle_time_ms=1.0,
                   fusion_threshold=64 << 20, cache_capacity=64,
                   stall_warning_sec=1.0, stall_shutdown_sec=0.0,
                   stall_check_enabled=True,
                   exec_callback=lambda resp, rid: core.response_done(
                       rid, False, "host-plane only"))
    assert ok, "native init failed"

    a = np.ones(8, np.float32)
    if rank == 0:
        # Submit and wait; rank 1 stalls deliberately for >1s.
        h = core.enqueue("stall.t", hn.OP_ALLREDUCE, 1, 7, a.shape,
                         data_ptr=a.ctypes.data, output_ptr=a.ctypes.data,
                         plane=hn.PLANE_HOST)
        # The coordinator must report the missing-rank tensor after the
        # 1s threshold (reference stall_inspector report contract,
        # test_stall.py:25 pattern).
        report = ""
        deadline = time.time() + 20
        while time.time() < deadline and "stall.t" not in report:
            time.sleep(0.5)
            report += core.stall_report()
        assert "stall.t" in report, f"no stall warning: {report!r}"
        r, err = core.wait(h); assert r == 1, err
    else:
        time.sleep(4.0)  # stall past the warning threshold
        h = core.enqueue("stall.t", hn.OP_ALLREDUCE, 1, 7, a.shape,
                         data_ptr=a.ctypes.data, output_ptr=a.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
    assert np.allclose(a, 2.0), a[:4]
    core.shutdown()
    print(f"STALL_{rank}_OK")
""")


def test_stall_warning_triggers_and_recovers(tmp_path):
    """One rank submits, the other stalls past the warning threshold:
    the coordinator's stall report names the missing tensor, and the
    collective still completes once the straggler arrives (reference
    test_stall.py — warn, don't kill, when shutdown_sec is 0)."""
    _run_workers(tmp_path, _STALL_WORKER, "STALL", size=2)


@pytest.mark.full
def test_adasum_vhdd_16_processes(tmp_path):
    """Deep-recursion VHDD: 16 ranks = 4 halving levels, peer links up
    to rank^8, scalar binomial trees spanning the full world — the
    controller, ring and pairwise planes all at the largest pow2 world
    this single-core machine can still schedule."""
    _run_workers(tmp_path, _ADASUM_WORKER, "ADASUM", size=16,
                 extra_args=(16,), timeout=360)


_JOIN_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=60.0, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    # Two steps with both ranks participating.
    for i in range(2):
        x = np.full(4, float(rank + 1), np.float32)
        h = core.enqueue(f"j.{i}", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
        assert np.allclose(x, 3.0), x

    # In-flight pre-join submission: rank 1 enqueues a tensor and joins
    # WITHOUT synchronizing (the reference supports outstanding ops across
    # join). The collective must wait for rank 0's matching submission and
    # carry rank 1's real data, not fire early or zero-fill.
    y = np.full(4, float(rank + 1), np.float32)
    if rank == 1:
        hy = core.enqueue("j.late", hn.OP_ALLREDUCE, 1, 7, y.shape,
                          data_ptr=y.ctypes.data, output_ptr=y.ctypes.data,
                          plane=hn.PLANE_HOST)
        # Depart early: block in join() while rank 0 keeps reducing.
        jh = core.join()
        r, err = core.wait(jh); assert r == 1, err
        r, err = core.wait(hy); assert r == 1, err
        assert np.allclose(y, 3.0), y
    else:
        import time
        time.sleep(0.3)  # let rank 1's submission + join land first
        hy = core.enqueue("j.late", hn.OP_ALLREDUCE, 1, 7, y.shape,
                          data_ptr=y.ctypes.data, output_ptr=y.ctypes.data,
                          plane=hn.PLANE_HOST)
        r, err = core.wait(hy); assert r == 1, err
        assert np.allclose(y, 3.0), y
        # Rank 0 runs five more allreduces to completion; the joined rank
        # contributes zeros (reference JoinOp semantics).
        for i in range(2, 7):
            x = np.full(4, 5.0, np.float32)
            h = core.enqueue(f"j.{i}", hn.OP_ALLREDUCE, 1, 7, x.shape,
                             data_ptr=x.ctypes.data,
                             output_ptr=x.ctypes.data, plane=hn.PLANE_HOST)
            r, err = core.wait(h); assert r == 1, err
            assert np.allclose(x, 5.0), x  # 5.0 + rank1's zeros
        # Allgather while a rank is joined must error loudly.
        d = np.ones(3, np.float32); out = np.zeros(6, np.float32)
        h = core.enqueue("j.ag", hn.OP_ALLGATHER, 1, 7, d.shape,
                         data_ptr=d.ctypes.data, output_ptr=out.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h)
        assert r == -1 and "not supported with Join" in err, (r, err)
        jh = core.join()
        r, err = core.wait(jh); assert r == 1, err
    # Rank 0 joined last on both sides' view.
    assert core.last_joined() == 0, core.last_joined()
    core.shutdown()
    print(f"JOIN_{rank}_OK")
""")


def test_join_zero_contribution_two_process(tmp_path):
    """Rank 1 joins after 2 steps; rank 0 completes 5 more allreduces with
    rank 1 contributing zeros, then joins. Parity: reference
    operations.cc:937-961, controller.cc:219-230,289-306."""
    _run_workers(tmp_path, _JOIN_WORKER, "JOIN")


def test_join_single_process(hvd):
    # Single-controller SPMD world: join degenerates to a barrier and
    # reports the last participant.
    assert hvd.join() == hvd.size() - 1


def test_ragged_host_allgatherv(tmp_path):
    """Ranks submit allgathers with differing first dimensions: the ring
    gathers with displacement math and the executor allocates the output
    from the response's per-rank dims (reference MPI_Allgatherv,
    ops/mpi_operations.cc:140-175)."""
    import textwrap as tw

    code = tw.dedent("""
        import os, sys
        import numpy as np
        sys.path.insert(0, os.environ["HVD_REPO"])
        from horovod_tpu.common import native as hn
        rank = int(sys.argv[1]); port = int(sys.argv[2])
        core = hn.NativeCore()
        assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
            cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
            coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
            fusion_threshold=64 << 20, cache_capacity=64,
            stall_warning_sec=60.0, stall_shutdown_sec=0.0,
            stall_check_enabled=True,
            exec_callback=lambda r, i: core.response_done(i, False, "n/a"))
        # rank 0: 3 rows of 2; rank 1: 5 rows of 2
        n = 3 if rank == 0 else 5
        d = np.full((n, 2), float(rank + 1), np.float32)
        h = core.enqueue("rag", hn.OP_ALLGATHER, 1, 7, d.shape,
                         data_ptr=d.ctypes.data, output_ptr=0,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h)
        assert r == 1, err
        raw, dims = core.result_fetch(h)
        assert dims == (3, 5), dims
        out = np.frombuffer(raw, np.float32).reshape(8, 2)
        assert np.allclose(out[:3], 1.0) and np.allclose(out[3:], 2.0), out
        # fetch erases the stored result
        assert core.result_fetch(h) is None
        # a 0-d host allgather is rejected loudly (reference parity)
        z = np.asarray(1.0, np.float32)
        hz = core.enqueue("rag0d", hn.OP_ALLGATHER, 1, 7, (),
                          data_ptr=z.ctypes.data, output_ptr=0,
                          plane=hn.PLANE_HOST)
        r, err = core.wait(hz)
        assert r == -1 and "rank-zero tensor" in err, (r, err)
        core.shutdown()
        print(f"RAGGED_{rank}_OK")
    """)
    _run_workers(tmp_path, code, "RAGGED")


_PARAM_SYNC_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=5.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=60.0, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    if rank == 0:
        # Coordinator's autotuner picks new parameters.
        core.set_parameters(2.5, 8 << 20)

    # Collectives drive negotiation cycles; the tuned values ride the
    # response broadcasts (Controller::SynchronizeParameters parity).
    for i in range(3):
        x = np.full(16, float(rank + 1), np.float32)
        h = core.enqueue(f"ps.{i}", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
        assert np.allclose(x, 3.0), x

    # Every rank — coordinator and worker — must converge on the tuned
    # (cycle_ms, fusion_bytes) pair.
    deadline = time.time() + 10.0
    while time.time() < deadline:
        cyc, fus = core.get_parameters()
        if abs(cyc - 2.5) < 1e-9 and fus == 8 << 20:
            break
        time.sleep(0.05)
    cyc, fus = core.get_parameters()
    assert abs(cyc - 2.5) < 1e-9, cyc
    assert fus == 8 << 20, fus
    core.shutdown()
    print(f"PARAMSYNC_{rank}_OK")
""")


def test_autotune_parameter_sync_two_process(tmp_path):
    """Coordinator-tuned (cycle_ms, fusion_bytes) propagate to worker ranks
    on the response broadcast. Parity: Controller::SynchronizeParameters,
    reference controller.cc:33-47."""
    _run_workers(tmp_path, _PARAM_SYNC_WORKER, "PARAMSYNC")


_STALL_WARN_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=0.5, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    x = np.full(4, float(rank + 1), np.float32)
    if rank == 0:
        h = core.enqueue("st.warn", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        # Coordinator warns once the tensor has waited past the threshold
        # with rank 1 missing (reference stall_inspector report,
        # test_stall.py:25).
        report = ""
        deadline = time.time() + 10.0
        while time.time() < deadline and "st.warn" not in report:
            report += core.stall_report()
            time.sleep(0.1)
        assert "Stalled tensor 'st.warn'" in report, report
        assert "missing ranks: [1]" in report, report
    else:
        time.sleep(2.0)  # stall past the 0.5 s warning threshold
        h = core.enqueue("st.warn", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
    r, err = core.wait(h); assert r == 1, err
    assert np.allclose(x, 3.0), x
    core.shutdown()
    print(f"STALLWARN_{rank}_OK")
""")


def test_stall_inspector_warning_two_process(tmp_path):
    """Asymmetric submission past the warning threshold produces a stall
    report naming the missing rank; the collective still completes when the
    straggler arrives. Parity: reference stall_inspector.cc, test_stall.py."""
    _run_workers(tmp_path, _STALL_WARN_WORKER, "STALLWARN")


_STALL_SHUTDOWN_WORKER = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=0.3, stall_shutdown_sec=1.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    if rank == 0:
        # Submit a tensor rank 1 never matches: after stall_shutdown_sec
        # the coordinator aborts the world and the pending handle resolves
        # with an abort status instead of hanging forever (reference
        # HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, stall_inspector.h:80).
        x = np.full(4, 1.0, np.float32)
        h = core.enqueue("st.dead", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h)
        assert r == -1, (r, err)
        assert "shut down" in err, err
    else:
        # Rank 1 submits nothing; it only needs to outlive the shutdown
        # threshold so its worker cycle receives the SHUTDOWN broadcast.
        time.sleep(3.0)
    core.shutdown()
    print(f"STALLDEAD_{rank}_OK")
""")


def test_stall_inspector_shutdown_two_process(tmp_path):
    """HOROVOD_STALL_SHUTDOWN parity: a stalled world hard-aborts after the
    shutdown threshold; waiters resolve with an abort error, no hang."""
    _run_workers(tmp_path, _STALL_SHUTDOWN_WORKER, "STALLDEAD")


_CACHE_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    # Tiny cache (capacity 4) so 8 distinct names force FIFO eviction
    # wraparound every round.
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=4,
        stall_warning_sec=60.0, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    # Phase 1: one hot tensor repeated 100x -> after the first trip every
    # submission rides the 4-byte cache id (reference response cache
    # fast path, response_cache.h:45-167).
    for i in range(100):
        x = np.full(8, float(rank + 1 + i), np.float32)
        h = core.enqueue("hot", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
        assert np.allclose(x, 3.0 + 2 * i), (i, x[:2])
    if rank != 0:
        hot_hits = core.cache_hits()
        assert hot_hits >= 90, hot_hits

    # Phase 2: 8 distinct names x 3 rounds with capacity 4 -> constant
    # eviction; ids must stay coherent across ranks (deterministic FIFO),
    # results must stay correct.
    for rnd in range(3):
        for t in range(8):
            x = np.full(4, float(rank + 1), np.float32)
            h = core.enqueue(f"evict.{t}", hn.OP_ALLREDUCE, 1, 7, x.shape,
                             data_ptr=x.ctypes.data,
                             output_ptr=x.ctypes.data, plane=hn.PLANE_HOST)
            r, err = core.wait(h); assert r == 1, err
            assert np.allclose(x, 3.0), (rnd, t, x)
    core.shutdown()
    print(f"CACHE_{rank}_OK")
""")


def test_response_cache_fast_path_and_eviction(tmp_path):
    """A repeated named allreduce takes the cache-id fast path (>=90/100
    submissions), and correctness holds through FIFO eviction wraparound
    with a capacity-4 cache. Parity: reference response_cache.cc +
    CoordinateCacheAndState."""
    _run_workers(tmp_path, _CACHE_WORKER, "CACHE", timeout=180)


_NEGOTIATION_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    rank = int(sys.argv[1]); port = int(sys.argv[2])
    core = hn.NativeCore()
    assert core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=60.0, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))

    if rank == 0:
        core.set_record_negotiation(True)
    for i in range(3):
        x = np.full(4, float(rank + 1), np.float32)
        h = core.enqueue(f"neg.{i}", hn.OP_ALLREDUCE, 1, 7, x.shape,
                         data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                         plane=hn.PLANE_HOST)
        r, err = core.wait(h); assert r == 1, err
    if rank == 0:
        # Coordinator saw one tick per (tensor, rank): both ranks on all
        # three tensors (reference NegotiateRankReady semantics).
        events = core.drain_negotiation()
        seen = {(e[0], e[2]) for e in events}
        for i in range(3):
            assert (0, f"neg.{i}") in seen, (i, events)
            assert (1, f"neg.{i}") in seen, (i, events)
        ts = [e[1] for e in events]
        assert all(t > 0 for t in ts)
        assert core.drain_negotiation() == []  # drained
    core.shutdown()
    print(f"NEG_{rank}_OK")
""")


def test_negotiation_rank_ready_ticks(tmp_path):
    """Per-rank negotiation ticks (reference Timeline::NegotiateRankReady,
    controller.cc:797-809): the coordinator records when each rank's
    submission arrived, queryable for the timeline."""
    _run_workers(tmp_path, _NEGOTIATION_WORKER, "NEG")


_JOBKEY_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, os.environ["HVD_REPO"])
    from horovod_tpu.common import native as hn

    idx = int(sys.argv[1]); port = int(sys.argv[2])
    # idx 0/1: a healthy 2-rank job with key jobA. idx 2: a stray worker
    # from another job (key jobB) claiming rank 1 — it must be rejected
    # WITHOUT killing the healthy job (the coordinator keeps accepting).
    os.environ["HOROVOD_JOB_KEY"] = "jobA" if idx < 2 else "jobB"
    rank = 1 if idx == 2 else idx
    if idx == 1:
        time.sleep(2.0)  # let the stray worker hit the coordinator first
    core = hn.NativeCore()
    ok = core.init(rank=rank, size=2, local_rank=0, local_size=1,
        cross_rank=rank, cross_size=2, coordinator_addr="127.0.0.1",
        coordinator_port=port, my_host="127.0.0.1", cycle_time_ms=1.0,
        fusion_threshold=64 << 20, cache_capacity=64,
        stall_warning_sec=60.0, stall_shutdown_sec=0.0,
        stall_check_enabled=True,
        exec_callback=lambda r, i: core.response_done(i, False, "n/a"))
    if idx == 2:
        assert not ok, "stray cross-job worker must be rejected"
        print(f"JOBKEY_{idx}_OK")
        sys.exit(0)
    assert ok, f"healthy rank {rank} failed to init"
    import numpy as np
    x = np.full(4, float(rank + 1), np.float32)
    h = core.enqueue("jk.ar", hn.OP_ALLREDUCE, 1, 7, x.shape,
                     data_ptr=x.ctypes.data, output_ptr=x.ctypes.data,
                     plane=hn.PLANE_HOST)
    r, err = core.wait(h); assert r == 1, err
    assert np.allclose(x, 3.0), x
    core.shutdown()
    print(f"JOBKEY_{idx}_OK")
""")


def test_job_key_rejects_cross_job_worker(tmp_path):
    """A stray worker from another job (wrong HOROVOD_JOB_KEY) is rejected
    loudly while the healthy job keeps accepting and completes its
    collectives."""
    _run_workers(tmp_path, _JOBKEY_WORKER, "JOBKEY", size=3)


def test_message_codec_robustness(tmp_path):
    """Builds and runs the C++ wire-codec harness (tests/csrc/
    test_message.cc): round-trips, malformed counts rejecting the whole
    frame (round-3 advisor finding — no misaligned parsing past a bad
    field), truncations, a deterministic mutation fuzz loop, the PR 4
    cross_rank hello/endpoint-map frame contract, the hostile-length
    allocation clamps, and the HOROVOD_MAX_FRAME_BYTES socket cap.

    Compiled on demand through the shared content-hash cache
    (tests/csrc_harness.py — the fuzz/golden drivers in test_hvdmc.py
    reuse the same binary): skips cleanly when no compiler is present,
    and runs under ASan+UBSan when the toolchain supports them (a codec
    fuzz loop without ASan misses the exact out-of-bounds reads it
    exists to catch)."""
    import subprocess

    import csrc_harness

    if csrc_harness.compiler() is None:
        pytest.skip("no C++ compiler on PATH")
    binary, sanitized = csrc_harness.build_codec_harness(tmp_path)
    env = {**os.environ, **csrc_harness.SANITIZER_ENV}
    r = subprocess.run([binary], capture_output=True, text=True,
                       timeout=240, env=env)
    report = r.stdout + r.stderr
    if sanitized and csrc_harness.sanitizer_report_broken(r.returncode,
                                                          report):
        # The ASan runtime itself failed to start (shadow-memory layout,
        # restricted personality, ...) before the harness ran a single
        # check: rerun the codec checks uninstrumented rather than fail
        # a codec that was never exercised.
        sanitized = False
        binary, _ = csrc_harness.build_codec_harness(tmp_path,
                                                     sanitize=False)
        r = subprocess.run([binary], capture_output=True, text=True,
                           timeout=240)
        report = r.stdout + r.stderr
    assert r.returncode == 0, report[-4000:]
    assert "MESSAGE_CODEC_OK" in r.stdout, report[-4000:]
    if sanitized:
        assert "ERROR: AddressSanitizer" not in report, report[-4000:]
        assert "runtime error:" not in report, report[-4000:]
