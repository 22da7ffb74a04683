"""Tensor-fusion v2 microbenchmark: monolithic vs bucketed train step.

Reports wall-time per step and the compiled all-reduce instruction count
for both configurations (the attribution pair: same model, same data,
only the fusion plan differs). Tier-1 safe: small model, few iterations,
and NO assertion that bucketed is faster — on 8 *virtual* CPU devices the
collectives are memcpys and overlap cannot win; the structural win is
asserted (instruction count), the timing is reported for trend tracking.
On real ICI such an A/B is a PR judged in the ``resnet50-dp4`` cell.

On jax 0.9 the structural win is gone: XLA's combiner packs the buckets
back into one all-reduce (strict xfail below; ROADMAP S5).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import flax.linen as nn

from hlo_text import (
    collective_instructions, collective_results, find_psums)
from horovod_tpu.training import (
    init_train_state, make_train_step, replicate_state, shard_batch)

WARMUP = 2
ITERS = 10
BUCKET_CAP = 64 * 1024


class BenchMLP(nn.Module):
    feats: tuple = (128,) * 11 + (10,)

    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        for f in self.feats:
            x = nn.Dense(f)(x)
            if f != self.feats[-1]:
                x = jax.nn.relu(x)
        return x


def _problem(hvd, bucket_cap):
    mesh = hvd.mesh()
    model = BenchMLP()
    opt = optax.sgd(0.1, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 64), jnp.float32)
    state = replicate_state(init_train_state(model, opt, rng, sample), mesh)
    imgs = jnp.asarray(
        np.random.RandomState(0).rand(32, 64).astype(np.float32))
    lbls = jnp.asarray(
        np.random.RandomState(1).randint(0, 10, 32).astype(np.int32))
    imgs, lbls = shard_batch((imgs, lbls), mesh)

    step = make_train_step(model, opt, mesh, bucket_cap_bytes=bucket_cap)
    return step, state, imgs, lbls


def _counts(step, *args):
    """What the step psums, what its compiled program reduces, and in
    how many all-reduce instructions."""
    jaxpr = jax.make_jaxpr(step)(*args)
    hlo = step.lower(*args).compile().as_text()
    return dict(
        psums=sum(len(b.eqns[i].invars) for b, i in find_psums(jaxpr.jaxpr)),
        reduced=len(collective_results(hlo)),
        instructions=len(collective_instructions(hlo)))


def _timed_run(hvd, bucket_cap):
    step, state, imgs, lbls = _problem(hvd, bucket_cap)
    counts = _counts(step, state, imgs, lbls)

    for _ in range(WARMUP):
        state, loss = step(state, imgs, lbls)
    float(np.asarray(loss))  # fence warmup/compile

    t0 = time.perf_counter()
    for _ in range(ITERS):
        state, loss = step(state, imgs, lbls)
    final_loss = float(np.asarray(loss))  # completion fence
    dt = (time.perf_counter() - t0) / ITERS
    return dt, counts, final_loss


def test_bucketed_vs_monolithic_step_time(hvd):
    dt_mono, n_mono, loss_mono = _timed_run(hvd, None)
    dt_buck, n_buck, loss_buck = _timed_run(hvd, BUCKET_CAP)

    # Same math (bitwise: partitioning an elementwise reduction).
    assert loss_mono == loss_buck

    # Every array the program psums is reduced in the compiled step, no
    # more and no fewer, whatever instructions XLA packs them into: one
    # fused gradient buffer + the loss pmean monolithic, one buffer per
    # bucket + the loss bucketed.
    assert n_mono["reduced"] == n_mono["psums"] == 2, n_mono
    assert n_buck["reduced"] == n_buck["psums"] > 2, n_buck

    # Timing is REPORTED, not gated (CPU virtual devices can't overlap);
    # shows up under -rP / -s and in CI logs for trend eyeballing.
    print(
        f"\nfusion-bench: monolithic {dt_mono * 1e3:.2f} ms/step "
        f"({n_mono['instructions']} all-reduce) | bucketed"
        f"[cap={BUCKET_CAP}B] {dt_buck * 1e3:.2f} ms/step "
        f"({n_buck['instructions']} all-reduce) | "
        f"ratio {dt_buck / dt_mono:.2f}x"
    )


@pytest.mark.xfail(strict=True, reason=(
    "jax 0.9 regression, ROADMAP S5: XLA's all-reduce combiner packs the "
    "buckets into one tuple all-reduce, so bucketing no longer multiplies "
    "the collectives the scheduler can place"))
def test_bucketing_multiplies_allreduce_instructions(hvd):
    """Structural assertion: bucketing multiplied the all-reduce
    instruction count."""
    n_mono = _counts(*_problem(hvd, None))
    n_buck = _counts(*_problem(hvd, BUCKET_CAP))
    assert n_buck["instructions"] > n_mono["instructions"], (n_mono, n_buck)
