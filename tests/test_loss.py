"""The next-token loss ``models/llama.py::_row_nll`` and its hand-written
gradient (PR 82), against the plain form JAX differentiates itself —
``-take_along_axis(log_softmax)``, the one ``tests/tiny_models.py`` keeps as
every model's reference — and what the gradient's jaxpr may not hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import _mean_nll, _row_nll, _weighted_nll

SEQ, VOCAB = 6, 37


def _plain_nll(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                targets[..., None], -1)[..., 0]


def _logits_targets(rows):
    kl, kt = jax.random.split(jax.random.PRNGKey(0))
    return (2.0 * jax.random.normal(kl, (rows, SEQ, VOCAB), jnp.float32),
            jax.random.randint(kt, (rows, SEQ), 0, VOCAB))


def _mean(nll, logits, targets):
    return jnp.mean(nll(logits, targets))


def _weighted(nll, logits, targets):
    """``_weighted_nll``'s sum: weights ``(b, s)``, some of them 0."""
    weights = jnp.arange(targets.size, dtype=jnp.float32).reshape(
        targets.shape) % 3
    return jnp.sum(nll(logits, targets) * weights) / targets.size


def _under_a_checkpoint(nll, logits, targets):
    """As ``_exit_reading`` runs it: the backward pass makes the rows'
    losses again from the logits it kept."""
    return jnp.sum(jax.checkpoint(
        lambda x: nll(x, targets), prevent_cse=False)(logits) ** 2)


CASES = {
    "several-rows": (_mean, 3, None),
    "one-row": (_mean, 1, None),
    "weighted-mean": (_weighted, 2, None),
    "target-at-0": (_mean, 2, 0),
    "target-at-the-last": (_mean, 2, VOCAB - 1),
    "under-a-checkpoint": (_under_a_checkpoint, 2, None),
}


@pytest.mark.parametrize("case", CASES)
def test_row_nll_is_the_plain_form_and_so_is_its_gradient(case):
    reduce, rows, target = CASES[case]
    logits, targets = _logits_targets(rows)
    if target is not None:
        targets = jnp.full_like(targets, target)
    (got, got_grad), (want, want_grad) = (
        jax.jit(jax.value_and_grad(
            lambda x: reduce(nll, x, targets)))(logits)
        for nll in (_row_nll, _plain_nll))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-6, atol=1e-6)
    assert np.abs(want_grad).max() > 1e-3     # a gradient, not zeros


def test_the_model_losses_are_means_of_row_nll():
    """``_mean_nll`` (plain, and over the positions a 0/1 weight keeps) and
    ``_weighted_nll`` are reductions of ``_row_nll`` and nothing else."""
    logits, targets = _logits_targets(2)
    nll = _plain_nll(logits, targets)
    keep = (jnp.arange(targets.shape[1]) < 4).astype(jnp.float32)
    weights = jnp.arange(targets.size, dtype=jnp.float32).reshape(
        targets.shape)
    np.testing.assert_allclose(_mean_nll(logits, targets), nll.mean(),
                               rtol=1e-6)
    np.testing.assert_allclose(_mean_nll(logits, targets, keep),
                               nll[:, :4].mean(), rtol=1e-6)
    np.testing.assert_allclose(_weighted_nll(logits, targets, weights),
                               (nll * weights).sum() / nll.size, rtol=1e-6)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("rows", [1, 3], ids=["one-row", "several-rows"])
@pytest.mark.parametrize("loss", ["mean", "kept-positions", "token",
                                  "weighted", "plain"])
def test_the_losses_gradient_gathers_and_scatters_nothing(loss, rows):
    """The gradient over the logits alone (the embedding's gather is not
    this test's): no ``gather`` going in, so no ``scatter-add`` coming
    back — on the TPU that scatter was float32 zeros the size of the
    logits, flat for a one-row batch (PERF.md §6, PR 30).  ``plain`` is the
    control: the form JAX differentiates itself holds both."""
    logits, targets = _logits_targets(rows)
    ones = jnp.ones(targets.shape, jnp.float32)
    of = {
        "mean": lambda x: _mean_nll(x, targets),
        "kept-positions": lambda x: _mean_nll(x, targets, ones[0]),
        # each position's loss, as a looped model's exits read it
        "token": lambda x: jnp.sum(_row_nll(x, targets) * ones),
        "weighted": lambda x: _weighted_nll(x, targets, ones),
        "plain": lambda x: jnp.mean(_plain_nll(x, targets)),
    }[loss]
    held = set(_primitives(jax.make_jaxpr(jax.grad(of))(logits).jaxpr))
    if loss == "plain":
        assert {"gather", "scatter-add"} <= held
    else:
        assert {"exp", "eq"} <= held
        assert not held & {"gather", "scatter", "scatter-add"}, held
