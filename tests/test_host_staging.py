"""HOROVOD_HOST_VIA_XLA: large host (torch) tensors ride the XLA plane.

2-process torch world with staging enabled: fused host allreduces above
the byte threshold are routed by the native cycle to the staging executor
(``common/host_staging.py``), which runs them as one compiled psum over a
one-device-per-process jax mesh; small tensors keep the TCP ring. The
timeline records ``XLA_ALLREDUCE`` for staged tensors — the proof the
fast-fabric path (not the ring) produced the asserted numbers.
"""

import json
import textwrap

from proc_harness import run_world

_WORKER = textwrap.dedent("""
    import os, sys
    rank = int(sys.argv[1]); port = int(sys.argv[2]); tl = sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["HOROVOD_SIZE"] = "2"
    os.environ["HOROVOD_RANK"] = str(rank)
    os.environ["HOROVOD_LOCAL_RANK"] = str(rank)
    os.environ["HOROVOD_LOCAL_SIZE"] = "2"
    os.environ["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
    os.environ["HOROVOD_CONTROLLER_PORT"] = str(port)
    os.environ["HOROVOD_CYCLE_TIME"] = "1.0"
    os.environ["HOROVOD_HOST_VIA_XLA"] = "1"
    os.environ["HOROVOD_HOST_VIA_XLA_THRESHOLD"] = "1024"
    if rank == 0:
        os.environ["HOROVOD_TIMELINE"] = tl
    sys.path.insert(0, os.environ["HVD_REPO"])

    import numpy as np
    import torch

    import horovod_tpu.torch as hvd

    hvd.init()
    assert hvd.size() == 2

    # Above threshold (400 KB): staged through the XLA plane.
    n = 100_000
    big = torch.arange(n, dtype=torch.float32) * (rank + 1)
    out = hvd.allreduce(big, name="big.grad", op=hvd.Sum)
    assert torch.allclose(out, torch.arange(n, dtype=torch.float32) * 3), \\
        out[:5]

    # Average (the default) above threshold.
    avg = hvd.allreduce(torch.full((2000,), float(rank + 1)),
                        name="big.avg")
    assert torch.allclose(avg, torch.full((2000,), 1.5)), avg[:5]

    # bf16 above threshold: fp32 accumulation inside the staged psum.
    bf = hvd.allreduce(
        torch.full((4096,), 1.0 + 2 ** -9, dtype=torch.bfloat16),
        name="big.bf16", op=hvd.Sum)
    assert bf.dtype == torch.bfloat16
    assert torch.allclose(bf.float(), torch.full((4096,), 2 * (1 + 2**-9)),
                          rtol=1e-2), bf[:5]

    # Ragged allgather above threshold (the IndexedSlices/sparse path):
    # rank 0 contributes 700 rows, rank 1 contributes 1100.
    nrows = 700 if rank == 0 else 1100
    g = torch.arange(nrows, dtype=torch.float32).reshape(nrows, 1) \
        + 1000 * rank
    gout = hvd.allgather(g, name="big.gather")
    expect = torch.cat([
        torch.arange(700, dtype=torch.float32).reshape(700, 1),
        torch.arange(1100, dtype=torch.float32).reshape(1100, 1) + 1000])
    assert gout.shape == (1800, 1), gout.shape
    assert torch.equal(gout, expect), gout[:3]

    # Broadcast above threshold (the broadcast_parameters startup path):
    # root 1's values must land everywhere via the staged psum.
    b = torch.arange(2000, dtype=torch.float32) * (rank + 1)
    bout = hvd.broadcast(b, root_rank=1, name="big.bcast")
    assert torch.allclose(bout, torch.arange(2000, dtype=torch.float32)
                          * 2), bout[:5]

    # Below threshold: stays on the ring, same math.
    small = hvd.allreduce(torch.full((10,), float(rank + 1)),
                          name="small.grad", op=hvd.Sum)
    assert torch.allclose(small, torch.full((10,), 3.0)), small

    # int64 above threshold: MUST stay on the ring (JAX canonicalizes
    # 64-bit buffers to 32 bits — staging would truncate). Values above
    # 2^31 prove full 64-bit fidelity end to end.
    i64 = torch.arange(2000, dtype=torch.int64) + (1 << 40) * (rank + 1)
    iout = hvd.allreduce(i64, name="big.i64", op=hvd.Sum)
    expect = 2 * torch.arange(2000, dtype=torch.int64) + 3 * (1 << 40)
    assert torch.equal(iout, expect), iout[:3]

    hvd.shutdown()
    print(f"STAGING_{rank}_OK")
""")


def test_bcast_plan_byte_parity():
    """The staged broadcast must move ~1x the payload per link (the
    psum-of-zeros formulation it replaced moves ~2x; the reference's NCCL
    broadcast is ~1x, nccl_operations.cc:369). The schedule's per-link
    traffic is steps * chunk elements — assert the overhead stays within
    the pipeline-tail bound for real payload sizes."""
    from horovod_tpu.common.host_staging import _bcast_plan

    for p in (2, 4, 8, 16, 64):
        for n in (1 << 17, 1 << 20, 10_000_000):  # >= the 1 MiB threshold
            num_chunks, chunk, padded, steps = _bcast_plan(n, p)
            per_link = steps * chunk
            assert padded >= n
            assert per_link <= 1.15 * n, (p, n, per_link)
            # And strictly better than the psum formulation's
            # reduce-scatter + all-gather (~2x (p-1)/p).
            assert per_link < 2 * n * (p - 1) / p or p == 2
    # Tiny payloads degrade to an unpipelined chain — still correct.
    num_chunks, chunk, padded, steps = _bcast_plan(64, 4)
    assert num_chunks == 1 and steps == 3


def test_ring_broadcast_program_multihop():
    """Pipeline correctness over an 8-device mesh (multi-hop chains,
    every root): each rank ends with exactly root's buffer."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.common.host_staging import build_ring_broadcast

    devs = jax.devices()
    p = len(devs)
    assert p == 8
    mesh = Mesh(np.array(devs, dtype=object), ("proc",))
    for n, root in ((1 << 12, 0), (1 << 12, 3), (1000, 7), (17, 5)):
        rows = np.zeros((p, n), np.float32)
        rows[root] = np.arange(n, dtype=np.float32) + 1.0
        arr = jax.device_put(
            jnp.asarray(rows), NamedSharding(mesh, P("proc")))
        prog = build_ring_broadcast(mesh, n, root, p)
        out = np.asarray(prog(arr))
        for r in range(p):
            np.testing.assert_array_equal(out[r], rows[root]), (r, root)


def test_host_via_xla_staging(tmp_path):
    tl = tmp_path / "timeline.json"
    run_world(tmp_path, _WORKER, "STAGING",
              args_for_rank=lambda rank, port: [str(port), str(tl)])

    # Rank 0's timeline must show the staged tensors on the XLA plane and
    # the small tensor NOT on it — the routing proof.
    text = tl.read_text().rstrip()
    if not text.endswith("]"):
        text = text.rstrip(",") + "\n]"
    events = json.loads(text)
    # thread_name metadata maps tensor names to tids; activity spans carry
    # the activity as the event name on that tid.
    tid_of = {e["args"]["name"]: e["tid"] for e in events
              if e.get("ph") == "M" and e.get("name") == "thread_name"}
    staged_tids = {e["tid"] for e in events
                   if e.get("name") == "XLA_ALLREDUCE"}
    assert staged_tids, \
        "no XLA_ALLREDUCE activity in the timeline — staging never ran"
    for name in ("big.grad", "big.avg", "big.bf16"):
        assert tid_of.get(name) in staged_tids, (name, tid_of, staged_tids)
    bcast_tids = {e["tid"] for e in events
                  if e.get("name") == "XLA_BROADCAST"}
    assert tid_of.get("big.bcast") in bcast_tids, (tid_of, bcast_tids)
    gather_tids = {e["tid"] for e in events
                   if e.get("name") == "XLA_ALLGATHER"}
    assert tid_of.get("big.gather") in gather_tids, (tid_of, gather_tids)
    # 64-bit tensors never stage (silent-truncation guard).
    if "big.i64" in tid_of:
        assert tid_of["big.i64"] not in staged_tids
    # The small tensor rode the ring: no XLA_ALLREDUCE span for it.
    if "small.grad" in tid_of:
        assert tid_of["small.grad"] not in staged_tids
