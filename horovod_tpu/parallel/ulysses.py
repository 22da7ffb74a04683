"""All-to-all sequence parallelism (Ulysses-style context parallelism).

The second first-class long-context strategy next to
:mod:`~horovod_tpu.parallel.ring_attention` (SURVEY §5 "Long-context /
sequence parallelism"; absent from the reference, which is DP-only).
Where ring attention keeps the sequence sharded and rotates K/V blocks
around the ``sp`` ring (sp - 1 ppermute steps, compute/transfer
overlapped), the all-to-all strategy re-shards once: an ``all_to_all``
swaps the sequence sharding for a head sharding, every chip runs plain
flash attention over the FULL sequence for its H/sp heads, and a second
``all_to_all`` swaps back.

Trade-offs (why both exist):

- **Bytes on the fabric**: all-to-all moves each Q/K/V element once
  (3 + 1 collectives of (sp-1)/sp of the local block each) — about half
  the ring's 2 x (sp-1) K/V block rotations. Better when attention
  compute is too short to hide the ring's rotations behind.
- **Constraint**: needs ``heads % sp == 0`` (after tp sharding). The
  ring has no head constraint and its working set stays T_local — the
  only option when the full sequence doesn't fit one chip's HBM.
- **Kernel shape**: local attention sees the full sequence, so the
  Pallas flash kernel runs at its natural tiling with a plain causal
  mask — no cross-block online-softmax merge.

Autodiff: ``lax.all_to_all`` is linear and differentiable; the backward
pass is the mirrored pair of all-to-alls around the flash backward — no
custom VJP needed.
"""

from __future__ import annotations

from jax import lax

from ..common.compat import axis_size as _axis_size


def gather_segment_ids(segment_ids, axis_name: str = "sp"):
    """All-gather sequence-sharded segment ids to [B, T_global].

    The gather is loop-invariant across decoder layers; callers running
    attention inside a layer scan (models/transformer.py) hoist it by
    gathering once and passing ``gathered_segment_ids`` — XLA does not
    lift collectives out of ``lax.scan`` bodies."""
    from jax import numpy as jnp

    return lax.all_gather(jnp.asarray(segment_ids, jnp.int32), axis_name,
                          axis=1, tiled=True)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                      segment_ids=None, gathered_segment_ids=None,
                      window=None):
    """Context-parallel attention via head<->sequence all-to-all.

    q: [B, T_local, H, D], k/v: [B, T_local, Hkv, D] per chip,
    sequence-sharded over ``axis_name``. Returns [B, T_local, H, D] with
    the same sharding.
    Requires ``H % axis_size == 0``. ``segment_ids`` (int [B, T_local],
    sequence-sharded like q): packed-sequence masking — after the
    re-shard every chip holds the full sequence, so the ids are simply
    all-gathered along it (or pass ``gathered_segment_ids`` [B, T_global]
    from :func:`gather_segment_ids` to hoist the gather out of a layer
    loop).
    """
    sp = _axis_size(axis_name)
    from jax import numpy as jnp

    from ..ops.pallas_attention import flash_attention

    heads = q.shape[2]
    if sp == 1:
        return flash_attention(q, k, v, causal=causal,
                               q_segment_ids=segment_ids,
                               k_segment_ids=segment_ids, window=window)
    if heads % sp != 0 or k.shape[2] % sp != 0:
        raise ValueError(
            f"ulysses_attention needs heads divisible by the '{axis_name}' "
            f"axis: {heads} query / {k.shape[2]} KV heads across {sp} "
            f"chips (after any tp head sharding). Use ring_attention "
            f"when heads don't divide.")

    # [B, T_local, H, D] -> [B, T_global, H/sp, D]: split the head axis
    # sp ways, concatenate the received blocks along the sequence axis.
    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    full_seg = gathered_segment_ids
    if full_seg is None and segment_ids is not None:
        full_seg = gather_segment_ids(segment_ids, axis_name)
    # GQA K/V cross the fabric at their own head count; the contiguous
    # head split means shard i's query heads use exactly shard i's KV
    # heads, which the kernels read as they are.
    o = flash_attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                        causal=causal, q_segment_ids=full_seg,
                        k_segment_ids=full_seg, window=window)
    return heads_to_seq(o)


def context_parallel_attention(q, k, v, axis_name: str = "sp",
                               causal: bool = True,
                               strategy: str = "ring",
                               segment_ids=None,
                               gathered_segment_ids=None, window=None):
    """Dispatch between the two sequence-parallel attention strategies.

    ``strategy``: ``"ring"`` (default — no head constraint, T_local
    working set), ``"ulysses"`` (all-to-all re-shard, needs
    heads % sp == 0), or ``"auto"`` (ulysses when the head constraint
    holds, ring otherwise). ``segment_ids``: packed-sequence masking,
    accepted by both strategies (``gathered_segment_ids`` additionally
    lets ulysses callers hoist the id gather out of a layer loop; the
    ring ignores it — its masking is block-local).
    """
    from .ring_attention import ring_attention

    if strategy == "auto":
        sp = _axis_size(axis_name)
        # Both query AND (GQA-reduced) KV heads must divide the axis for
        # ulysses' head split; otherwise fall back to ring as documented.
        strategy = ("ulysses" if q.shape[2] % sp == 0
                    and k.shape[2] % sp == 0 else "ring")
    if strategy == "ulysses":
        return ulysses_attention(q, k, v, axis_name=axis_name,
                                 causal=causal, segment_ids=segment_ids,
                                 gathered_segment_ids=gathered_segment_ids,
                                 window=window)
    if strategy == "ring":
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              segment_ids=segment_ids, window=window)
    raise ValueError(f"unknown sequence-parallel strategy {strategy!r}; "
                     "expected 'ring', 'ulysses', or 'auto'")
