"""The ZeRO memory contract, measured: per-device live bytes of
params + optimizer state scale ~1/d at stage 3.

Byte accounting is over the state's committed device buffers
(``addressable_shards`` on one device) — the steady-state footprint a
training loop actually holds between steps. Transients (the gathered
bucket in flight, the scatter payload) are bounded by the bucket cap and
are the price of the step, not the residency; ``step_mem_GiB`` of a
benchmark cell would count them (no cell runs ZeRO yet: PERF.md, 7).

The analytic model this pins (plain fp32 SGD, no momentum):

    stage 1/2 per device:  P (replicated params) + P/d (master shard)
    stage 3   per device:  P/d (master shard only)

    ratio = (P/d) / (P + P/d) = 1/(d+1)  <=  1/d

so the acceptance gate ``ratio <= 1/d + eps`` holds with analytic margin.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from horovod_tpu.training import shard_batch  # noqa: E402
from horovod_tpu.zero import (  # noqa: E402
    init_zero_train_state, make_zero_train_step)


def _mlp():
    """Every leaf's size divisible by 8 (the test mesh width): 16->64
    kernel 1024, biases 64, 64->8 kernel 512, bias 8 — zero padding, so
    the measured ratio is EXACTLY the analytic one."""
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(64)(x))
            return nn.Dense(8)(x)

    return MLP()


def _dev_bytes(tree, dev):
    """Bytes of ``tree``'s committed buffers resident on ``dev``."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        for s in leaf.addressable_shards:
            if s.device == dev:
                total += s.data.size * s.data.dtype.itemsize
    return total


def _problem(hvd, stage, opt):
    mesh = hvd.mesh()
    model = _mlp()
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 16), jnp.float32)
    state = init_zero_train_state(model, opt, rng, sample, mesh,
                                  zero_stage=stage)
    step = make_zero_train_step(model, opt, mesh, zero_stage=stage)
    imgs = jnp.asarray(
        np.random.RandomState(0).rand(16, 16).astype(np.float32))
    lbls = jnp.asarray(
        np.random.RandomState(1).randint(0, 8, 16).astype(np.int32))
    imgs, lbls = shard_batch((imgs, lbls), mesh)
    return state, step, imgs, lbls


def test_stage3_state_bytes_shrink_1_over_d(hvd):
    """THE acceptance gate: stage-3 per-device param+state bytes are
    <= (1/d + eps) of stage 1's — measured, both at init and in the
    donation steady state after real steps."""
    d = hvd.size()
    dev = jax.devices()[0]
    opt = optax.sgd(0.1)  # stateless: the crisp 1/(d+1) memory model

    s1, step1, imgs, lbls = _problem(hvd, 1, opt)
    s3, step3, _, _ = _problem(hvd, 3, opt)

    def footprint(state):
        # params + masters + optimizer state; the scalar stamps (step,
        # bucket_cap, stage) ride along at a few bytes.
        return _dev_bytes(state, dev)

    eps = 0.02
    b1, b3 = footprint(s1), footprint(s3)
    assert b3 / b1 <= 1.0 / d + eps, (b1, b3)
    # Zero padding by construction -> the analytic 1/(d+1), up to the
    # 12 bytes of int32 stamps (step/bucket_cap/stage) in both states.
    np.testing.assert_allclose(b3 / b1, 1.0 / (d + 1), atol=0.002)

    for _ in range(2):
        s1, _ = step1(s1, imgs, lbls)
        s3, _ = step3(s3, imgs, lbls)
    b1s, b3s = footprint(s1), footprint(s3)
    assert b3s / b1s <= 1.0 / d + eps, (b1s, b3s)


def test_stage3_holds_zero_replicated_param_bytes(hvd):
    """The parameter partition itself: stage-3 params contribute ZERO
    device bytes (shape template), and total parameter storage across
    stages compares as P (replicated, per device) vs P/d (shard)."""
    d = hvd.size()
    dev = jax.devices()[0]
    opt = optax.sgd(0.1)
    s1, _, _, _ = _problem(hvd, 1, opt)
    s3, _, _, _ = _problem(hvd, 3, opt)

    assert _dev_bytes(s3.params, dev) == 0
    p_full = _dev_bytes(s1.params, dev)
    p_shard = _dev_bytes(s3.pshard, dev)
    # fp32 model: the master shard is exactly 1/d of the replicated tree.
    assert p_shard * d == p_full, (p_shard, p_full)


def test_stage3_momentum_state_also_sharded(hvd):
    """With momentum the optimizer shard doubles the per-device state at
    BOTH ends — the ratio becomes 2/(d+2), still O(1/d)."""
    d = hvd.size()
    dev = jax.devices()[0]
    opt = optax.sgd(0.1, momentum=0.9)
    s1, _, _, _ = _problem(hvd, 1, opt)
    s3, _, _, _ = _problem(hvd, 3, opt)
    b1, b3 = _dev_bytes(s1, dev), _dev_bytes(s3, dev)
    np.testing.assert_allclose(b3 / b1, 2.0 / (d + 2), rtol=0.01)
