"""``models/transformer.token_nll``: the decoder's cross-entropy from the
logits and a per-token log-sum-exp, with a hand-written backward pass.
Its value and gradient against plain ``log_softmax`` + ``take_along_axis``
in float32 on the CPU, and what the head and loss keep for the backward
pass: the logits and nothing else of their size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (
    TransformerConfig, init_params, make_loss_fn, shard_params, token_nll)
from horovod_tpu.parallel.mesh import build_parallel_mesh


def _plain_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _random_logits(shape, scale=3.0, seed=0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


def _labels(shape, vocab, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab,
                              jnp.int32)


def _case_bf16_hidden():
    # As the decoder's head makes them: bf16 hidden states, float32 matmul.
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    hidden = jax.random.normal(k1, (2, 8, 32), jnp.bfloat16)
    head = jax.random.normal(k2, (32, 96), jnp.float32) / 4
    logits = jnp.einsum("btd,dv->btv", hidden.astype(jnp.float32), head)
    return logits, _labels((2, 8), 96), None


def _case_last_real_row():
    # GPT-2's table: 50,257 rows padded to 50,304; the last real token.
    logits = _random_logits((2, 4, 50304))
    return logits, jnp.full((2, 4), 50256, jnp.int32), None


def _case_equal_logits():
    logits = _random_logits((2, 8, 96)).at[0, 3].set(0.25).at[1, 0].set(-7.0)
    return logits, _labels((2, 8), 96), None


def _case_magnitude_1e4():
    # Without the max-shift exp overflows; with the log-sum-exp kept as
    # one float32 the probabilities would be 1e-3 off.
    logits = _random_logits((2, 8, 96), scale=1e4)
    return logits, _labels((2, 8), 96), None


def _case_weighted():
    weights = jax.random.uniform(jax.random.PRNGKey(3), (2, 8), jnp.float32,
                                 0.0, 2.0).at[0, 0].set(0.0)
    return _random_logits((2, 8, 96)), _labels((2, 8), 96), weights


CASES = {
    "bf16_hidden_upstream": _case_bf16_hidden,
    "last_real_row_of_padded_table": _case_last_real_row,
    "row_of_equal_logits": _case_equal_logits,
    "logits_of_magnitude_1e4": _case_magnitude_1e4,
    "cotangent_not_uniform": _case_weighted,
}


@pytest.mark.parametrize("case", CASES)
def test_value_and_gradient_match_log_softmax(case):
    logits, labels, weights = CASES[case]()
    if weights is None:
        weights = jnp.full(labels.shape, 1.0 / labels.size, jnp.float32)

    def total(nll):
        return lambda x: jnp.sum(weights * nll(x, labels))

    got = jax.jit(token_nll)(logits, labels)
    want = jax.jit(_plain_nll)(logits, labels)
    assert got.dtype == jnp.float32 and got.shape == labels.shape
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    got_grad = jax.jit(jax.grad(total(token_nll)))(logits)
    want_grad = jax.jit(jax.grad(total(_plain_nll)))(logits)
    assert got_grad.dtype == jnp.float32
    assert np.all(np.isfinite(got_grad))
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-6, atol=1e-6)


def _is_logits_sized(aval, shape):
    return tuple(aval.shape) == shape and aval.dtype == jnp.float32


def test_head_and_loss_keep_the_logits_and_nothing_else_of_their_size():
    """The decoder's own loss on a one-device mesh, with a vocabulary no
    other axis shares: of float32 [b, t, V] arrays the backward pass is
    handed one, the logits, and it scatters into none."""
    cfg = TransformerConfig(vocab=112, d_model=32, n_heads=2, d_head=16,
                            d_ff=64, n_layers=2, max_seq=16)
    mesh = build_parallel_mesh(jax.devices()[:1], sp=1, tp=1, pp=1)
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), 1), cfg,
                          mesh)
    tokens = _labels((4, 16), cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    loss_fn = make_loss_fn(cfg, mesh, n_microbatches=1)
    btv = tokens.shape + (cfg.vocab,)

    # Inside the shard_map, where the residuals are cut: its forward half
    # returns them, so they are outputs of the one shard_map equation of
    # the linearised loss.
    _, f_vjp = jax.vjp(lambda p: loss_fn(p, tokens, labels), params)
    kept = [jax.typeof(x) for x in jax.tree_util.tree_leaves(f_vjp)]
    assert sum(_is_logits_sized(a, btv) for a in kept) == 1, kept

    backward = jax.make_jaxpr(jax.grad(loss_fn))(params, tokens, labels)
    scatters = [
        eqn for eqn in _all_eqns(backward.jaxpr)
        if eqn.primitive.name.startswith("scatter")]
    # The embedding's transpose scatters into its table; nothing scatters
    # into an array over the vocabulary axis of the logits.
    assert scatters, "the embedding's gradient is a scatter-add"
    for eqn in scatters:
        assert not any(_is_logits_sized(v.aval, btv) for v in eqn.outvars)
    made = [v.aval for eqn in _all_eqns(backward.jaxpr) for v in eqn.outvars]
    assert any(_is_logits_sized(a, btv) for a in made)  # the walk saw them


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)
