"""Multi-host XLA plane: a real 2-process ``jax.distributed`` world.

This is SURVEY §4 Pattern 1 applied to the TPU production path: on a pod,
``hvd.init()`` joins a multi-process JAX world
(``common/state.py:_maybe_init_distributed``) and every eager collective
crosses processes through ``jax.make_array_from_process_local_data``
(``ops/eager.py:_to_global_sharded``). Every other multi-process test in
this suite drives the host TCP ring; these two processes drive the XLA
plane itself — each with 2 virtual CPU devices, so the world is 4
participants across 2 processes, exercising the same global-mesh SPMD
programs that span ICI+DCN on real hardware.
"""

import textwrap

import pytest

from proc_harness import run_world

_PRELUDE = textwrap.dedent("""
    import os, sys
    rank = int(sys.argv[1]); port = int(sys.argv[2])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
    os.environ["HOROVOD_SIZE"] = "2"
    os.environ["HOROVOD_RANK"] = str(rank)
    os.environ["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
    os.environ["HOROVOD_CONTROLLER_PORT"] = str(port)
    os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"
    sys.path.insert(0, os.environ["HVD_REPO"])

    import numpy as np
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    assert jax.process_count() == 2, jax.process_count()
    assert hvd.size() == 4, hvd.size()
    assert hvd.local_size() == 2, hvd.local_size()
    assert hvd.cross_size() == 2, hvd.cross_size()
    # Participant ids are device-order: process 0 owns 0,1; process 1
    # owns 2,3.
    my_ranks = [2 * rank, 2 * rank + 1]
    assert hvd.rank() == my_ranks[0], hvd.rank()
""")


def test_eager_collectives_cross_process(tmp_path):
    """allreduce/allgather/broadcast on jax arrays across 2 processes."""
    script = _PRELUDE + textwrap.dedent("""
        # --- allreduce (Sum): participants carry their global rank ---
        xs = [jnp.full((5,), float(r), jnp.float32) for r in my_ranks]
        out = hvd.allreduce(xs, op=hvd.Sum, name="mh.ar")
        for o in out:
            np.testing.assert_allclose(np.asarray(o), 0 + 1 + 2 + 3)

        # --- allreduce (Average, default) ---
        out = hvd.allreduce([jnp.full((3,), float(r + 1), jnp.float32)
                             for r in my_ranks], name="mh.avg")
        for o in out:
            np.testing.assert_allclose(np.asarray(o), 2.5)

        # --- allgather: concat along dim 0 in participant order ---
        xs = [jnp.full((2, 3), float(r), jnp.float32) for r in my_ranks]
        got = np.asarray(hvd.allgather(xs, name="mh.ag"))
        expect = np.concatenate(
            [np.full((2, 3), float(r), np.float32) for r in range(4)])
        np.testing.assert_allclose(got, expect)

        # --- broadcast from participant 2 (first chip of process 1) ---
        xs = [jnp.full((4,), float(r), jnp.float32) for r in my_ranks]
        out = hvd.broadcast(xs, 2, name="mh.bc")
        for o in out:
            np.testing.assert_allclose(np.asarray(o), 2.0)

        # --- reducescatter: each participant keeps its 1/4 of the sum ---
        xs = [jnp.arange(8, dtype=jnp.float32) + r for r in my_ranks]
        out = hvd.reducescatter(xs, op=hvd.Sum, name="mh.rs")
        full = sum(np.arange(8, dtype=np.float32) + r for r in range(4))
        for o, r in zip(out, my_ranks):
            np.testing.assert_allclose(np.asarray(o),
                                       full[2 * r: 2 * (r + 1)])

        # --- alltoall: participant p's j-th slice lands on participant j
        xs = [jnp.arange(4, dtype=jnp.float32) * 10 + r for r in my_ranks]
        out = hvd.alltoall(xs, name="mh.a2a")
        for o, r in zip(out, my_ranks):
            np.testing.assert_allclose(
                np.asarray(o), np.array([10.0 * r + p for p in range(4)]))

        # --- Adasum (pow2 world) vs the NumPy oracle: non-parallel
        # per-rank vectors so a silent fallback to Sum/Average would fail.
        from horovod_tpu.ops.adasum import adasum_reference

        def vec(r):
            v = np.zeros(6, np.float32)
            v[r] = 2.0 + r
            v[(r + 1) % 6] = 1.0
            return v

        xs = [jnp.asarray(vec(r)) for r in my_ranks]
        out = hvd.allreduce(xs, op=hvd.Adasum, name="mh.adasum")
        expect = adasum_reference([vec(r) for r in range(4)])
        for o in out:
            np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-4)

        hvd.shutdown()
        print(f"MULTIHOST_{rank}_OK")
    """)
    run_world(tmp_path, script, "MULTIHOST")


def test_hierarchical_dispatch_cross_process(tmp_path):
    """HOROVOD_HIERARCHICAL_ALLREDUCE/ALLGATHER across 2 processes: the
    (cross, local) mesh genuinely spans a process boundary here — local
    reduce-scatter inside each process's chips, cross leg between
    processes — the ICI x DCN split the hierarchical variants model."""
    script = _PRELUDE.replace(
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"',
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"\n'
        'os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"\n'
        'os.environ["HOROVOD_HIERARCHICAL_ALLGATHER"] = "1"'
    ) + textwrap.dedent("""
        # hier_mesh exists for any homogeneous world; the CONFIG flags are
        # the actual dispatch gate (a silently failed prelude-replace must
        # not leave this test green on the flat path).
        from horovod_tpu.common.state import global_state
        assert hvd.hierarchical_mesh() is not None
        assert global_state().config.hierarchical_allreduce
        assert global_state().config.hierarchical_allgather

        xs = [jnp.full((8,), float(r + 1), jnp.float32) for r in my_ranks]
        out = hvd.allreduce(xs, op=hvd.Sum, name="mh.har")
        for o in out:
            np.testing.assert_allclose(np.asarray(o), 1 + 2 + 3 + 4)

        xs = [jnp.full((3, 2), float(r), jnp.float32) for r in my_ranks]
        got = np.asarray(hvd.allgather(xs, name="mh.hag"))
        expect = np.concatenate(
            [np.full((3, 2), float(r), np.float32) for r in range(4)])
        np.testing.assert_allclose(got, expect)

        # --- hierarchical Adasum (reference AdasumGpu semantics: plain
        # sum inside each process's LOCAL group, Adasum across the two
        # processes). Non-parallel per-rank vectors so both a fallback
        # to flat Adasum and a fallback to Sum/Average would fail.
        from horovod_tpu.ops.adasum import hierarchical_adasum_reference

        def hvec(r):
            v = np.zeros(6, np.float32)
            v[r] = 2.0 + r
            v[(r + 3) % 6] = 1.0
            return v

        xs = [jnp.asarray(hvec(r)) for r in my_ranks]
        out = hvd.allreduce(xs, op=hvd.Adasum, name="mh.hadasum")
        expect = hierarchical_adasum_reference(
            [hvec(r) for r in range(4)], local_size=2)
        for o in out:
            np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-4)

        hvd.shutdown()
        print(f"MHHIER_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHHIER")


def test_join_cross_process(tmp_path):
    """hvd.join across the 2-process XLA-plane world: process 1 runs one
    more allreduce than process 0; the joined process 0 contributes
    zeros (the JoinOp AllocateZeros role) so process 1's collective
    completes, and join returns the last joiner's rank everywhere."""
    script = _PRELUDE + textwrap.dedent("""
        out = hvd.allreduce(
            [jnp.full((4,), float(r + 1), jnp.float32) for r in my_ranks],
            op=hvd.Sum, name="mh.pre")
        np.testing.assert_allclose(np.asarray(out[0]), 1 + 2 + 3 + 4)

        if rank == 1:
            # The straggler: one extra allreduce after rank 0 joined —
            # rank 0's zero contribution must complete it.
            extra = hvd.allreduce(
                [jnp.full((4,), 5.0, jnp.float32) for _ in my_ranks],
                op=hvd.Sum, name="mh.extra")
            np.testing.assert_allclose(np.asarray(extra[0]), 5.0 + 5.0)
        # Process 1 deterministically joins last (its extra allreduce
        # precedes its join); join returns the last joiner's global
        # PARTICIPANT rank, which must be one of process 1's chips —
        # and identically on every process.
        last = hvd.join()
        assert last in (2, 3), last

        hvd.shutdown()
        print(f"MHJOIN_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHJOIN")


def test_autotune_categorical_sync_cross_process(tmp_path):
    """The tuner's categorical hierarchical decision must reach every
    rank: the coordinator grid-samples the four combos, the pinned flags
    ride the response broadcast, and the WORKER's native core reports the
    same applied value."""
    script = _PRELUDE.replace(
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"',
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"\n'
        'os.environ["HOROVOD_AUTOTUNE"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] = "2"'
    ) + textwrap.dedent("""
        from horovod_tpu.common.state import global_state

        st = global_state()
        assert st.cross_size == 2
        if rank == 0:
            assert st.autotuner is not None

        # warmup(1) + categorical grid(4) + GP(2) samples at 1 step each.
        for i in range(10):
            out = hvd.allreduce(
                [jnp.full((16,), float(r + i), jnp.float32)
                 for r in my_ranks], op=hvd.Sum, name=f"tune.{i}")
            np.testing.assert_allclose(np.asarray(out[0]),
                                       sum(range(4)) + 4 * i)

        flags = st.engine.native_core.get_hier_flags()
        assert flags >= 0, flags  # synced decision arrived on this rank
        if rank == 0:
            assert st.autotuner.hier_flags == flags

        hvd.shutdown()
        print(f"MHTUNE_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHTUNE")


def test_grouped_vs_tuned_hier_coherence_cross_process(tmp_path):
    """Autotune coherence proof for grouped/direct-mode traffic (VERDICT
    r4 #7): while the tuner's categorical sampling flips the
    hierarchical flags for cycle-fused traffic (frame-stamped, applied
    identically on every rank), grouped_allreduce_async deliberately
    follows the STATIC config only — a mid-tune flip must never compile
    divergent SPMD programs across ranks for interleaved grouped calls.

    The proof is two-layered: (1) the interleaved schedule completes
    with correct numbers on both processes — divergent hier-vs-flat
    programs across ranks would wedge or corrupt the collective; (2) the
    engine's program cache records the hier variant in each key, and
    every grouped-path program (distinguished by its shapes) compiled
    with hier=False on every rank, even on samples where the tuner
    pinned hierarchical=on for the cycle-fused shapes."""
    script = _PRELUDE.replace(
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"',
        'os.environ["HOROVOD_HOSTNAME"] = "127.0.0.1"\n'
        'os.environ["HOROVOD_AUTOTUNE"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] = "1"\n'
        'os.environ["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] = "2"'
    ) + textwrap.dedent("""
        from horovod_tpu.common.state import global_state

        st = global_state()
        assert st.hier_mesh is not None  # tuner explores hier combos

        # Interleave cycle-fused traffic (shape 16 — the tuner's grid
        # walks warmup + 4 categorical combos + GP samples across these)
        # with grouped/direct submissions (shapes 7 and 9).
        for i in range(10):
            out = hvd.allreduce(
                [jnp.full((16,), float(r + i), jnp.float32)
                 for r in my_ranks], op=hvd.Sum, name=f"coh.{i}")
            np.testing.assert_allclose(np.asarray(out[0]),
                                       sum(range(4)) + 4 * i)
            h = hvd.grouped_allreduce_async(
                [[jnp.full((7,), float(r + i), jnp.float32)
                  for r in my_ranks],
                 [jnp.full((9,), 2.0 * (r + i), jnp.float32)
                  for r in my_ranks]], op=hvd.Sum, name=f"cohg.{i}")
            ga, gb = hvd.synchronize(h)
            np.testing.assert_allclose(np.asarray(ga[0]),
                                       sum(range(4)) + 4 * i)
            np.testing.assert_allclose(np.asarray(gb[0]),
                                       2.0 * (sum(range(4)) + 4 * i))

        # The tuner's synced decision reached this rank (the flip
        # actually happened — otherwise this test proves nothing).
        flags = st.engine.native_core.get_hier_flags()
        assert flags >= 0, flags

        # Program-cache audit: grouped/direct programs (shapes (7,),(9,))
        # must ALL be the static-config variant (hier=False); only the
        # cycle-fused shape (16,) may have compiled a hier variant.
        grouped_keys = [
            k for k in st.engine._program_cache
            if k[0] == "grouped_allreduce"
            and any(s == (7,) for s, _ in k[1])
        ]
        assert grouped_keys, "grouped programs never compiled"
        for k in grouped_keys:
            assert k[-1] is False, f"grouped program used hier: {k}"
        # Positive control: the flip genuinely happened — the tuner's
        # categorical grid pins hier=on for some samples, so the
        # cycle-fused shape must have compiled a hier=True variant. If
        # the frame-stamping plumbing regressed to always-flat, the
        # grouped audit above would pass vacuously; this catches that.
        assert any(
            k[0] == "grouped_allreduce"
            and any(s == (16,) for s, _ in k[1]) and k[-1] is True
            for k in st.engine._program_cache
        ), "cycle-fused traffic never compiled a hier variant"

        hvd.shutdown()
        print(f"MHCOH_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHCOH")


def test_ragged_allgather_multi_chip_cross_process(tmp_path):
    """Ragged first dims on chips of BOTH processes (local_size 2): the
    per-chip dim table (Request.chip_dims -> response first_dims) drives
    the global pad+gather+slice."""
    script = _PRELUDE + textwrap.dedent("""
        # Chip c contributes (c+1) rows: proc0 chips 1,2 rows; proc1 3,4.
        # Submitted three times with the same name — training loops repeat
        # names every step, and a response-cache replay that dropped the
        # per-chip dims would corrupt every pass after the first.
        expect = np.concatenate(
            [np.full((r + 1, 3), float(r), np.float32) for r in range(4)])
        for _ in range(3):
            xs = [jnp.full((r + 1, 3), float(r), jnp.float32)
                  for r in my_ranks]
            got = np.asarray(hvd.allgather(xs, name="mh.rag"))
            assert got.shape == expect.shape, (got.shape, expect.shape)
            np.testing.assert_allclose(got, expect)

        # Mixed: one process ragged, the other equal-dims, same collective.
        if rank == 0:
            ys = [jnp.full((2, 2), 0.0, jnp.float32),
                  jnp.full((5, 2), 1.0, jnp.float32)]
        else:
            ys = [jnp.full((3, 2), 2.0, jnp.float32),
                  jnp.full((3, 2), 3.0, jnp.float32)]
        got = np.asarray(hvd.allgather(ys, name="mh.rag2"))
        sizes = [2, 5, 3, 3]
        expect = np.concatenate(
            [np.full((sizes[c], 2), float(c), np.float32)
             for c in range(4)])
        np.testing.assert_allclose(got, expect)

        hvd.shutdown()
        print(f"MHRAGGED_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHRAGGED")


@pytest.mark.full
def test_randomized_schedule_cross_process(tmp_path):
    """Soak for the multi-host XLA plane: a deterministic pseudo-random
    schedule of mixed collectives (both ranks generate the same schedule
    from a shared seed) stresses negotiation ordering, the response
    cache across repeated names, and fusion across processes."""
    script = _PRELUDE + textwrap.dedent("""
        import random

        r_sched = random.Random(1234)  # same schedule on both processes
        for step in range(30):
            op = r_sched.choice(["ar", "ag", "bc"])
            n = r_sched.randint(1, 64)
            name = f"soak.{op}.{step % 7}"  # names repeat: cache hits
            xs = [jnp.full((n,), float(r + step), jnp.float32)
                  for r in my_ranks]
            if op == "ar":
                out = hvd.allreduce(xs, op=hvd.Sum, name=name)
                for o in out:  # both local chips, full values
                    np.testing.assert_allclose(
                        np.asarray(o), sum(range(4)) + 4 * step)
            elif op == "ag":
                got = np.asarray(hvd.allgather(xs, name=name))
                expect = np.concatenate(
                    [np.full((n,), float(r + step), np.float32)
                     for r in range(4)])
                np.testing.assert_allclose(got, expect)
            else:
                root = r_sched.randint(0, 3)
                out = hvd.broadcast(xs, root, name=name)
                for o in out:
                    np.testing.assert_allclose(np.asarray(o),
                                               float(root + step))

        hvd.shutdown()
        print(f"MHSOAK_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHSOAK", timeout=420)


@pytest.mark.full
def test_sequence_parallel_attention_cross_process(tmp_path):
    """Ring AND Ulysses context-parallel attention with the sp axis
    spanning a real process boundary: 4 sequence shards over 2 processes,
    so ppermute rotations / all_to_all re-shards cross the
    ``jax.distributed`` fabric the way they cross DCN on a pod."""
    script = _PRELUDE + textwrap.dedent("""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from horovod_tpu.parallel.ring_attention import ring_attention
        from horovod_tpu.parallel.ulysses import ulysses_attention

        B, T, H, D = 2, 16, 4, 8
        rng = np.random.RandomState(0)
        q, k, v = (rng.randn(B, T, H, D).astype(np.float32)
                   for _ in range(3))

        # Dense causal oracle, computed identically on both processes.
        s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64),
                      k.astype(np.float64)) / np.sqrt(D)
        s = np.where(np.tril(np.ones((T, T), bool))[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expected = np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))

        mesh = Mesh(np.array(jax.devices()), ("sp",))
        sharding = NamedSharding(mesh, P(None, "sp"))

        def to_global(x):
            return jax.make_array_from_callback(
                x.shape, sharding, lambda idx: x[idx])

        qa, ka, va = (to_global(t) for t in (q, k, v))
        for name, attn in (("ring", ring_attention),
                           ("ulysses", ulysses_attention)):
            fn = jax.jit(jax.shard_map(
                lambda q, k, v, a=attn: a(q, k, v, "sp", causal=True),
                mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
                check_vma=False))
            out = fn(qa, ka, va)
            for shard in out.addressable_shards:
                np.testing.assert_allclose(
                    np.asarray(shard.data), expected[shard.index],
                    rtol=2e-4, atol=2e-5,
                    err_msg=f"{name} shard {shard.index} mismatch")

        # Packed sequences across the process boundary: segment ids
        # shard with the tokens; the ring rotates the K-side ids through
        # the distributed fabric.
        seg = np.stack([np.repeat([0, 1, 2], [5, 6, 5]),
                        np.repeat([0, 1], [9, 7])]).astype(np.int32)
        allowed = (np.tril(np.ones((T, T), bool))[None, None]
                   & (seg[:, None, :, None] == seg[:, None, None, :]))
        s2 = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64),
                       k.astype(np.float64)) / np.sqrt(D)
        s2 = np.where(allowed, s2, -np.inf)
        p2 = np.exp(s2 - s2.max(-1, keepdims=True))
        p2 /= p2.sum(-1, keepdims=True)
        expected_seg = np.einsum("bhqk,bkhd->bqhd", p2,
                                 v.astype(np.float64))
        sega = to_global(seg)
        for name, attn in (("ring", ring_attention),
                           ("ulysses", ulysses_attention)):
            fn = jax.jit(jax.shard_map(
                lambda q, k, v, s, a=attn: a(q, k, v, "sp", causal=True,
                                             segment_ids=s),
                mesh=mesh, in_specs=(P(None, "sp"),) * 4,
                out_specs=P(None, "sp"), check_vma=False))
            out = fn(qa, ka, va, sega)
            for shard in out.addressable_shards:
                np.testing.assert_allclose(
                    np.asarray(shard.data), expected_seg[shard.index],
                    rtol=2e-4, atol=2e-5,
                    err_msg=f"seg {name} shard {shard.index} mismatch")

        hvd.shutdown()
        print(f"MHSEQ_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHSEQ", timeout=420)


@pytest.mark.full
def test_model_parallel_transformer_cross_process(tmp_path):
    """The full 4-axis (dp,pp,sp,tp) transformer train step with the mesh
    spanning a real 2-process ``jax.distributed`` world — pipeline,
    context-parallel attention, tensor sharding, and the ZeRO-over-dp
    optimizer partitioning all crossing the process boundary in one
    compiled program."""
    script = _PRELUDE + textwrap.dedent("""
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from horovod_tpu.models.transformer import (
            TransformerConfig, init_params, make_train_step, shard_params)
        from horovod_tpu.parallel.mesh import build_parallel_mesh
        from horovod_tpu.training import init_opt_state

        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, d_head=8,
                                d_ff=64, n_layers=2, max_seq=16)
        mesh = build_parallel_mesh(jax.devices(), dp=2, pp=1, sp=2, tp=1)
        params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1),
                              cfg, mesh)
        opt = optax.adam(1e-3)
        opt_state = init_opt_state(opt, params, mesh, zero_axis="dp")
        opt_shardings = jax.tree_util.tree_map(lambda x: x.sharding,
                                               opt_state)
        step = make_train_step(cfg, opt, mesh, n_microbatches=1,
                               opt_shardings=opt_shardings)

        B, T = 4, 16
        rngd = np.random.RandomState(0)
        sharding = NamedSharding(mesh, P("dp", "sp"))
        tok_host = rngd.randint(0, cfg.vocab, (B, T)).astype(np.int32)
        lab_host = rngd.randint(0, cfg.vocab, (B, T)).astype(np.int32)
        tokens = jax.make_array_from_callback(
            (B, T), sharding, lambda idx: tok_host[idx])
        labels = jax.make_array_from_callback(
            (B, T), sharding, lambda idx: lab_host[idx])

        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           labels)
            losses.append(float(np.asarray(loss)))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses  # training moves
        # The dp-partitioned moments survive the cross-process step.
        assert "dp" in list(opt_state[0].mu["wqkv"].sharding.spec)

        hvd.shutdown()
        print(f"MHTF_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHTF", timeout=420)


@pytest.mark.full
def test_jax_state_sync_cross_process(tmp_path):
    """JaxState.sync() with a REAL broadcast_object across 2 processes:
    the coordinator's committed snapshot (tree + attrs) reaches the
    peer in one message and is re-placed on each process's mesh view."""
    script = _PRELUDE + textwrap.dedent("""
        from horovod_tpu.elastic import JaxState

        # Divergent initial trees: only rank 0's must survive sync.
        tree = {"w": jnp.arange(8.0) * (rank + 1)}
        state = JaxState(tree, batch=100 * (rank + 1))
        state.sync()
        np.testing.assert_array_equal(np.asarray(state.tree["w"]),
                                      np.arange(8.0))
        assert state.batch == 100, state.batch
        # The synced point is the committed point.
        state.tree = {"w": state.tree["w"] * 5.0}
        state.batch = 7
        state.restore()
        np.testing.assert_array_equal(np.asarray(state.tree["w"]),
                                      np.arange(8.0))
        assert state.batch == 100, state.batch

        hvd.shutdown()
        print(f"MHJST_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHJST", timeout=420)


@pytest.mark.full
def test_train_step_and_zero_cross_process(tmp_path):
    """One DP train step and one ZeRO-1 step through the global mesh."""
    script = _PRELUDE + textwrap.dedent("""
        import optax
        from horovod_tpu.models.resnet import ResNet18
        from horovod_tpu.training import (
            init_train_state, make_train_step, replicate_state, shard_batch)
        from horovod_tpu.zero import (
            init_zero_train_state, make_zero_train_step)

        mesh = hvd.mesh()
        model = ResNet18(num_classes=10, dtype=jnp.bfloat16)
        opt = optax.sgd(0.01)
        rng = jax.random.PRNGKey(0)
        sample = jnp.zeros((1, 32, 32, 3), jnp.float32)

        # Every process builds the same global batch; shard_batch hands
        # each process its addressable slices of the global array.
        imgs = np.random.RandomState(0).rand(8, 32, 32, 3).astype(np.float32)
        lbls = np.random.RandomState(1).randint(0, 10, 8).astype(np.int32)
        imgs, lbls = shard_batch((jnp.asarray(imgs), jnp.asarray(lbls)), mesh)

        state = replicate_state(init_train_state(model, opt, rng, sample),
                                mesh)
        step = make_train_step(model, opt, mesh)
        state, loss = step(state, imgs, lbls)
        loss0 = float(np.asarray(loss))
        assert np.isfinite(loss0), loss0
        state, loss = step(state, imgs, lbls)
        assert float(np.asarray(loss)) < loss0 + 1.0  # sane progression

        # --- ZeRO-1 step over the same global mesh ---
        zstate = init_zero_train_state(model, opt, rng, sample, mesh)
        zstep = make_zero_train_step(model, opt, mesh)
        zstate, zloss = zstep(zstate, imgs, lbls)
        np.testing.assert_allclose(float(np.asarray(zloss)), loss0,
                                   rtol=5e-2)

        hvd.shutdown()
        print(f"MHTRAIN_{rank}_OK")
    """)
    run_world(tmp_path, script, "MHTRAIN", timeout=420)
