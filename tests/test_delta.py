"""The gated delta rule (``ops/delta.py``) and the hybrid model it makes
possible (``models/llama.py``: linear-attention and full-attention layers in
the published three-to-one pattern, QK-norm, NoPE, a norm on what each
block ADDS), on the CPU at tiny sizes in float32.  The model's yardstick is
the benchmark's own plain reference (``benchmark/reference/olmo_hybrid.py``:
the recurrence a token at a time, nothing shared with the code under
test)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid
from ray_tpu.models import llama
from ray_tpu.models.blocks import delta
from ray_tpu.models.blocks.delta import GDN_STATE_ABSMAX
from ray_tpu.models.llama import (
    LlamaConfig, init_params, loss_fn, param_logical_axes)
from ray_tpu.ops import delta as delta_ops
from ray_tpu.ops.delta import (
    delta_chunked, delta_kernels, delta_reference, delta_xla, kernels_fit,
    unit_lower_inverse)
from ray_tpu.ops.layers import rms_norm, swiglu
from ray_tpu.ops.ssm import causal_conv1d
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import batch_shard_map
from ray_tpu.train.core import STEP_SCOPES

import tiny_models
from conftest import compiled_to_run
from tiny_models import (
    GDN_SCOPES, ROWS, against_the_reference, program, reference, side_of,
    train_step_reports)

HIGHEST = jax.default_matmul_precision("highest")


def _rule_inputs(seq, neg_eigval, seed=0, batch=2, heads=3, dk=12, dv=24,
                 dtype=jnp.float32):
    """q and k as the mixer hands them over (unit length a head, q times
    ``dk ** -0.5``), head sizes in the published 1 : 2, decays of up to a
    third a token, beta in (0, 2) with the eigenvalue switch, and a state
    that is carried in."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k = unit(f(batch, seq, heads, dk)) * dk ** -0.5, unit(
        f(batch, seq, heads, dk))
    g = -0.3 * jax.nn.softplus(f(batch, seq, heads))
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(f(batch, seq, heads))
    return (q.astype(dtype), k.astype(dtype), f(batch, seq, heads, dv).astype(
        dtype), g, beta, f(batch, heads, dv, dk))


def _rule_value_and_grads(form, args, weight):
    """What ``form(*args)`` returns and the gradients of a weighted sum of
    its output and state to all six arguments: ONE compiled program."""
    def scalar(*t):
        out = form(*t)
        o, state = out[:2]
        return jnp.sum(o * weight) + 0.1 * jnp.sum(jnp.square(state)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=range(6), has_aux=True))(*args)
    return out, grads


SMALL = dict(heads=3, dk=12, dv=24)        # no kernel fits: the XLA form
PUBLISHED = dict(batch=1, heads=2, dk=96, dv=192)   # Olmo-Hybrid's heads


def _takes_the_kernels(form, args):
    """Whether ``form``'s program holds the forward kernel: the dispatch
    as it can be observed."""
    return "delta_fwd" in str(jax.make_jaxpr(form)(*args))


@pytest.mark.parametrize("neg_eigval", [True, False],
                         ids=["beta-to-2", "beta-to-1"])
@pytest.mark.parametrize("seq,chunk,sizes,kernels", [
    (100, 16, SMALL, False), (128, 64, SMALL, False), (24, 64, SMALL, False),
    (200, 64, PUBLISHED, True), (128, 64, PUBLISHED, True),
    (64, 64, dict(batch=2, heads=1, dk=32, dv=64), True)],
    ids=["ragged-16", "two-chunks-64", "shorter-than-a-chunk",
         "kernels-ragged-published", "kernels-one-pair-published",
         "kernels-one-chunk-32-64"])
def test_delta_chunked_equals_the_recurrence(seq, chunk, sizes, kernels,
                                             neg_eigval):
    """Values, the state handed on and the gradient of every input — q, k,
    v, the log-decay, beta and the state carried in — against the
    recurrence a token at a time, with and without negative eigenvalues:
    the XLA form at two chunk sizes, on a sequence no chunk divides (padded
    with tokens that neither decay nor write) and one shorter than a chunk;
    the Pallas kernels (interpreted here) at the published head sizes, keys
    96 and values 192, on a sequence that is no multiple of the chunk, on
    one pair of chunks, and on ONE chunk of smaller heads (the other half
    of its pair is padding).  Nothing but the shapes chooses the form, and
    the program shows which ran.  Float32 against float32 in another order
    of sums: 2e-5 on values, 2e-4 of each gradient's scale."""
    args = _rule_inputs(seq, neg_eigval, **sizes)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    chunked = lambda *t: delta_chunked(*t, chunk=chunk)
    assert _takes_the_kernels(chunked, args) == kernels == kernels_fit(
        sizes["dk"], sizes["dv"], min(chunk, seq))
    with HIGHEST:
        ((o, state, peak), grads), ((want, want_state), want_grads) = (
            _rule_value_and_grads(f, args, weight)
            for f in (chunked, delta_reference))
    assert o.shape == want.shape and o.dtype == args[2].dtype
    np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)
    assert float(peak) >= float(jnp.max(jnp.abs(want_state))) - 2e-5
    for name, g, w in zip("q k v g beta state".split(), grads, want_grads):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.max(jnp.abs(
            w))), rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)],
                         ids=["bfloat16", "float32"])
def test_the_kernels_equal_the_xla_form_at_the_published_sizes(dtype, tol):
    """Both forms of ONE algorithm on the same arguments — keys 96, values
    192, ``beta`` up to 2, a state that enters, a sequence of three chunks
    and a half —: the kernels round where the XLA form rounds (the
    operands of the big products to ``q.dtype``, everything of the decays,
    the inverse and the state in float32), so in bfloat16 the outputs, the
    last state and the largest state agree far inside bfloat16's rounding,
    and each of the six gradients (the backward kernel, which the
    benchmark's check never runs) to 2e-2 of its scale; in float32 to
    2e-5.  The output has v's dtype, the state is float32."""
    args = _rule_inputs(224, True, seed=4, dtype=dtype, **PUBLISHED)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    assert _takes_the_kernels(delta_chunked, args)
    assert not _takes_the_kernels(delta_xla, args)
    with HIGHEST:
        ((o, state, peak), grads), (
            (want, want_state, want_peak), want_grads) = (
                _rule_value_and_grads(f, args, weight)
                for f in (delta_kernels, delta_xla))
    assert o.dtype == dtype and state.dtype == jnp.float32
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    scale = float(np.max(np.abs(f32(want))))
    np.testing.assert_allclose(f32(o), f32(want), atol=tol * scale)
    np.testing.assert_allclose(state, want_state, atol=tol * float(
        jnp.max(jnp.abs(want_state))))
    np.testing.assert_allclose(peak, want_peak, rtol=tol)
    for name, g, w in zip("q k v g beta state".split(), grads, want_grads):
        assert g.dtype == w.dtype and np.all(np.isfinite(f32(g))), name
        np.testing.assert_allclose(f32(g), f32(w), atol=tol * float(np.max(
            np.abs(f32(w)))), err_msg=name)


@pytest.mark.parametrize("key_dim,value_dim,chunk,fits", [
    (96, 192, 64, True),      # the published heads
    (128, 128, 64, True), (32, 64, 64, True), (64, 256, 64, True),
    (96, 192, 16, False),     # the kernels are written for chunks of 64
    (96, 192, 128, False),
    (96, 192, 24, False),     # a sequence shorter than a chunk
    (12, 24, 64, False),      # keys under a sublane tile of bfloat16
    (100, 192, 64, False),    # keys that fill no whole tiles
    (256, 192, 64, False),    # keys past one lane block
    (96, 200, 64, False), (96, 512, 64, False)])
def test_kernels_fit_says_what_the_kernels_were_written_for(
        key_dim, value_dim, chunk, fits):
    assert kernels_fit(key_dim, value_dim, chunk) == fits


def test_the_result_does_not_depend_on_the_chunk():
    """Chunks of 8, 16 and 64 give one output and one last state; what
    differs is where a chunk ends, so the largest state SEEN there may."""
    args = _rule_inputs(96, True, seed=3)
    with HIGHEST:
        results = [jax.jit(lambda *t, c=c: delta_chunked(*t, chunk=c))(*args)
                   for c in (8, 16, 64)]
    for o, state, _ in results[1:]:
        np.testing.assert_allclose(o, results[0][0], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(state, results[0][1], atol=2e-5,
                                   rtol=2e-5)


def test_unit_lower_inverse_is_the_inverse_with_its_gradient():
    """Blocks of two rows merged by doubling, against ``jnp.linalg.inv``
    at 64 rows (and at 24 and 7, whose last block is short at some level),
    and its written backward pass against autodiff of the solve; an entry
    on or above the diagonal gets no gradient."""
    rng = np.random.default_rng(0)
    for n in (64, 24, 7):
        a = jnp.tril(jnp.asarray(rng.normal(size=(3, n, n)) * 0.2,
                                 jnp.float32), -1)
        weight = jnp.asarray(rng.normal(size=(3, n, n)), jnp.float32)
        with HIGHEST:
            want = jnp.linalg.inv(jnp.eye(n) + a)
            np.testing.assert_allclose(unit_lower_inverse(a), want,
                                       atol=1e-4, rtol=1e-4)
            got = jax.grad(lambda t: jnp.sum(unit_lower_inverse(t) * weight)
                           )(a)
            auto = jax.grad(lambda t: jnp.sum(jnp.linalg.inv(
                jnp.eye(n) + jnp.tril(t, -1)) * weight))(a)
        np.testing.assert_allclose(got, auto, atol=1e-3, rtol=1e-3)
        assert not np.any(np.triu(np.asarray(got)))


def test_the_inverse_holds_where_the_plain_series_loses_it():
    """Why no finite series ``(I - A)(I + A^2)...(I + A^32)`` runs.  A
    chunk whose keys lean one way (after a SiLU they do) with ``beta`` near
    1 or near 2 and little decay: ``A``'s entries are all near 0.5 or 0.9,
    its powers reach 1e9 and more before they cancel, and the six-factor
    series at 64 rows is off by hundreds in float32 where the inverse
    itself stays under 2; by doubling the same matrix inverts to 2e-5 and
    1e-3 (against numpy in float64)."""
    def series(a):
        inv, power = jnp.eye(64, dtype=jnp.float32) - a, a
        for _ in range(5):
            power = power @ power
            inv = inv + inv @ power
        return inv

    rng = np.random.default_rng(0)
    for level, atol in ((0.5, 2e-5), (0.9, 1e-3)):
        a = np.tril(level + 0.05 * rng.normal(size=(64, 64)), -1)
        want = np.linalg.inv(np.eye(64) + a)
        assert np.abs(want).max() < 2.0        # the rule keeps it bounded
        with HIGHEST:
            got = np.asarray(unit_lower_inverse(jnp.asarray(a, jnp.float32)))
            lost = np.asarray(series(jnp.asarray(a, jnp.float32)))
        np.testing.assert_allclose(got, want, atol=atol)
        assert not np.abs(lost - want).max() < 100.0


# -- the kernels' pair: its inverse, its MXU passes, its pairs written out ----

def _pairs_of(first, second):
    """Chunks' ``(64, 64)`` matrices as pairs: ``first[i]``, ``second[i]``
    on the block diagonal of ``(128, 128)``."""
    a = np.zeros((len(first), 128, 128))
    a[:, :64, :64], a[:, 64:, 64:] = first, second
    return a


def _leaning_pairs():
    """``test_the_inverse_holds_where_the_plain_series_loses_it``'s two
    matrices (entries near 0.5 and near 0.9), each beside the other."""
    rng = np.random.default_rng(0)
    low, high = (np.tril(level + 0.05 * rng.normal(size=(64, 64)), -1)
                 for level in (0.5, 0.9))
    return _pairs_of([low, high], [high, low])


def _coinciding_pairs():
    """Chunks as ``tests/test_kda.py::test_a_write_strength_over_one_on_
    keys_that_nearly_coincide`` draws them: one direction a chunk with 0.05
    of noise on it, ``beta`` in (1, 2), a decay of 0.01 a token — ``beta k
    k^T`` decayed, entries up to 2."""
    rng = np.random.default_rng(7)

    def chunk():
        k = rng.normal(size=(1, 128)) + 0.05 * rng.normal(size=(64, 128))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        beta = 1.0 + 1.0 / (1.0 + np.exp(-rng.normal(size=(64, 1))))
        cum = np.cumsum(-0.01 * np.log1p(np.exp(rng.normal(size=64))))
        return np.tril(beta * (k @ k.T) * np.exp(cum[:, None] - cum), -1)

    return _pairs_of([chunk() for _ in range(4)], [chunk() for _ in range(4)])


def _in_a_kernel(inverse, a):
    """``inverse(a[i], row, col)`` for every pair of ``a (n, 128, 128)``
    inside a Pallas body (interpreted), as the forward kernels call it."""
    from jax.experimental import pallas as pl

    def body(a_ref, out_ref):
        row, col = (delta_ops._iota((128, 128), axis) for axis in (0, 1))
        out_ref[0] = inverse(a_ref[0], row, col)

    one = pl.BlockSpec((1, 128, 128), lambda i: (i, 0, 0))
    return pl.pallas_call(
        body, grid=(a.shape[0],), in_specs=[one], out_specs=one,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=True)(a)


@pytest.mark.parametrize("pairs", [_leaning_pairs, _coinciding_pairs],
                         ids=["keys-that-lean", "keys-that-coincide"])
def test_the_pair_inverse_is_the_inverse_of_either_chunk(pairs):
    """``_pair_inverse`` — the doubling on a PAIR's block-diagonal ``(128,
    128)``, as both forward kernels call it — on the matrices that break
    the plain series and on ``beta`` up to 2 over keys that nearly
    coincide: against numpy's inverse in float64 its largest error is held
    to TWICE what ``unit_lower_inverse`` reads on the same chunks (the same
    ten products at ``Precision.HIGHEST``: 2.3e-7 and 1.5e-6 here), not to
    a tolerance — the bar for any other way of making those products (PR
    80 measured one: three packed bfloat16 passes a product read 1.6e-7
    and 2.4e-6, and no faster).  Nothing is left outside the two blocks or
    above their diagonals, and the diagonal is 1."""
    a = jnp.asarray(pairs(), jnp.float32)
    want = np.linalg.inv(np.eye(128) + np.asarray(a, np.float64))
    assert np.abs(want).max() < 2.0
    got = np.asarray(_in_a_kernel(delta_ops._pair_inverse, a))
    halves = (slice(0, 64), slice(64, 128))
    with HIGHEST:
        plain = [np.asarray(unit_lower_inverse(a[:, half, half]))
                 for half in halves]
    held_to = 2.0 * max(np.abs(t - want[:, half, half]).max()
                        for t, half in zip(plain, halves))
    assert held_to < 1e-5
    assert np.abs(got - want).max() <= held_to
    for t, half in zip(plain, halves):
        np.testing.assert_allclose(got[:, half, half], t, atol=held_to)
    assert not np.any(got[:, :64, 64:]) and not np.any(got[:, 64:, :64])
    assert not np.any(np.triu(got, 1))
    assert np.all(np.diagonal(got, axis1=1, axis2=2) == 1.0)


def _kernel_jaxpr(kernel, keys, values, s=512):
    """The jaxpr of one of the four kernels' calls at bfloat16 heads of
    ``keys`` and ``values``, two heads, ``s`` tokens (one grid step of four
    pairs)."""
    f32 = jnp.float32
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (1, 2, *dims), dtype)
    if kernel.startswith("delta"):     # scalar rows; a state a CHUNK saved
        args = (shape(keys, s), shape(keys, s), shape(values, s),
                shape(2, s, dtype=f32))
        saved = shape(s // 64, keys, values, dtype=f32)
    else:                              # decays a channel; a state a STEP
        args = (*[shape(keys, s)] * 3, shape(keys, s, dtype=f32),
                shape(1, s, dtype=f32))
        saved = shape(1, keys, values, dtype=f32)
    state = shape(keys, values, dtype=f32)
    if kernel.endswith("bwd"):         # + the inverses, do, dh_last
        args += (saved, shape(s, 128), shape(values, s))
    call = {"delta_fwd": delta_ops._fwd_call, "delta_bwd": delta_ops._bwd_call,
            "kdarule_fwd": delta_ops._kda_fwd_call,
            "kdarule_bwd": delta_ops._kda_bwd_call}[kernel]
    jaxpr = jax.make_jaxpr(functools.partial(call, interpret=True))(
        *args, state)
    assert kernel in str(jaxpr)
    return jaxpr.jaxpr


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, a loop's body
    once."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _mxu_passes(jaxpr):
    """``(passes, float32 products at HIGHEST)`` of ``jaxpr``'s
    ``dot_general``s: ``(m, k) x (k, n)`` is ``ceil(m / 128) ceil(k / 128)
    ceil(n / 128)`` passes of the MXU, six times that for float32 operands
    at ``Precision.HIGHEST``."""
    passes = highest = 0
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        lhs, rhs = (v.aval for v in eqn.invars)
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        m = np.prod([d for i, d in enumerate(lhs.shape)
                     if i not in (*lc, *lb)], dtype=int)
        n = np.prod([d for i, d in enumerate(rhs.shape)
                     if i not in (*rc, *rb)], dtype=int)
        k = np.prod([lhs.shape[i] for i in lc], dtype=int)
        tiles = int(np.prod([-(-d // 128) for d in (m, k, n)]))
        precision = eqn.params["precision"]
        full = lhs.dtype == jnp.float32 and jax.lax.Precision.HIGHEST in (
            precision if isinstance(precision, tuple) else (precision,))
        passes, highest = passes + tiles * (6 if full else 1), highest + full
    return passes, highest


@pytest.mark.parametrize("kernel,keys,values,passes", [
    ("delta_fwd", 96, 192, 79), ("kdarule_fwd", 128, 128, 81)])
def test_sixty_of_a_pairs_mxu_passes_are_the_inverses(
        kernel, keys, values, passes):
    """The MXU passes a PAIR of chunks costs a forward kernel, counted
    from its jaxpr at the published head sizes in bfloat16 (the pair
    loop's body counts once): 79 and 81, of which the inverse's ten
    float32 products at ``Precision.HIGHEST`` are sixty and the only
    float32 products.  (PR 80 built them as thirty packed bfloat16 passes
    — 49 and 51 a pair — and the kernels ran no faster: a pair is a chain
    of products that wait on each other, not a count of passes.  Whoever
    moves these numbers measures the kernels alone first.)"""
    assert _mxu_passes(_kernel_jaxpr(kernel, keys, values)) == (passes, 10)


@pytest.mark.parametrize("kernel,keys,values,loops", [
    ("delta_fwd", 96, 192, 1), ("delta_bwd", 96, 192, 1),
    ("kdarule_fwd", 128, 128, 1), ("kdarule_bwd", 128, 128, 2)])
def test_a_grid_steps_pairs_are_written_out(kernel, keys, values, loops):
    """Every loop over a grid step's pairs in the four kernels (the
    per-channel backward has two: its forward sweep, then the walk in
    reverse) is written out in full (``_each_pair``): the next pair's
    decays, products and inverse have nothing to wait for in this pair's
    walk through the state, and inside a loop they waited all the same —
    8.49 -> 7.28 us a pair forward + backward at Kimi-Linear's layer shape
    on the chip, every result to the bit (PR 80)."""
    scans = [eqn for eqn in _eqns(_kernel_jaxpr(kernel, keys, values))
             if eqn.primitive.name in ("scan", "while")]
    assert len(scans) == loops
    assert all(eqn.primitive.name == "scan" and eqn.params["length"] == 4
               and eqn.params["unroll"] == 4 for eqn in scans)


def test_delta_chunked_in_bfloat16_keeps_its_decays_and_state_in_float32():
    """Operands of the big products in the input's dtype, the decays, the
    inverse and the carried state in float32: a bfloat16 call stays within
    bfloat16's rounding of the float32 one (a relative 2 ** -8 an operand,
    a few operands deep: 3e-2 of the output's scale), the state it hands
    on is float32, and the output has v's dtype."""
    args = _rule_inputs(128, True, seed=2)
    half = tuple(t.astype(jnp.bfloat16) for t in args[:3]) + args[3:]
    want, want_state, _ = delta_chunked(*args, chunk=64)
    o, state, _ = delta_chunked(*half, chunk=64)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) < 3e-2 * scale
    assert float(jnp.max(jnp.abs(state - want_state))) < 3e-2 * float(
        jnp.max(jnp.abs(want_state)))


def test_causal_conv1d_without_a_bias():
    """``bias=None``, given or left out, is the convolution with a bias of
    zeros: values and gradients."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 19, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zeros = jnp.zeros((6,), jnp.float32)
    np.testing.assert_allclose(causal_conv1d(x, w), causal_conv1d(x, w, zeros),
                               atol=1e-6)
    np.testing.assert_allclose(causal_conv1d(x, w, None),
                               causal_conv1d(x, w, zeros), atol=1e-6)
    got = jax.grad(lambda x_, w_: jnp.sum(jnp.sin(causal_conv1d(x_, w_))),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x_, w_: jnp.sum(jnp.sin(causal_conv1d(
        x_, w_, zeros))), argnums=(0, 1))(x, w)
    for g, v in zip(got, want):
        np.testing.assert_allclose(g, v, atol=1e-6)


# ---- the model --------------------------------------------------------

TOKENS = ROWS["olmo_hybrid"].tokens
_cfg = functools.partial(tiny_models.tiny, "olmo_hybrid")
_drawn = functools.partial(tiny_models.drawn, seed=5)
# float32 against float32, relative: the loss a mean of 192 numbers near
# 5.5, the gradients through three recurrences of 96 tokens in two orders
LOSS_TOL, NLL_TOL, GRAD_TOL = 2e-6, 2e-5, 5e-4


def test_config_names_the_published_pattern():
    cfg = _cfg()
    assert cfg.layer_runs == (("linear_attention", 3), ("full_attention", 1))
    assert (cfg.gdn_key_inner, cfg.gdn_value_inner, cfg.gdn_conv_dim) == (
        32, 64, 128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    linear, full = params["layers"]
    assert linear["gdn_in"].shape == (3, 64, 32 + 32 + 64 + 64 + 4 + 4)
    assert linear["gdn_conv_w"].shape == (3, 4, 128)
    assert "gdn_conv_b" not in linear and linear["gdn_gate_norm"].shape == (
        3, 16)
    assert full["q_norm"].shape == (1, 64) and "lm_head" in params
    axes = param_logical_axes(cfg)["layers"][0]
    assert axes["gdn_in"] == ("layer", "kernel_in", "gdn_inner")
    assert jax.tree.structure(axes, is_leaf=lambda t: isinstance(
        t, tuple)) == jax.tree.structure(linear)
    with pytest.raises(ValueError):
        _cfg(block_norm="both")
    with pytest.raises(NotImplementedError):
        _cfg(num_experts=4)


def test_hybrid_loss_token_losses_and_gradients_equal_the_plain_reference():
    """linear x 3, full through ``loss_fn`` (the flash kernel interpreted,
    the layer checkpoint on) against the benchmark's reference, which
    computes the recurrence a token at a time: the loss within 2e-6, each
    position's loss within 2e-5 nats, every gradient leaf within 5e-4 of
    its scale."""
    ours = program("olmo_hybrid")
    _, metrics, want, _ = against_the_reference(
        "olmo_hybrid", parts=(), rtol=LOSS_TOL, nll_atol=None,
        grad_rtol=GRAD_TOL)
    # 2e-5 is the OPTIMISED arithmetic's bound (9.5e-6 read): compiled to
    # check, the same program sums in another order and one token of 192
    # reads 2.48e-5 — so this one comparison is of a side built here
    with compiled_to_run():
        np.testing.assert_allclose(
            side_of("olmo_hybrid", ours.cfg, ours.params).token_nll(
                ours.params), want["token_nll"], atol=NLL_TOL)
    with HIGHEST:
        _, aux = jax.jit(lambda p: llama.forward(
            p, TOKENS[:, :-1], ours.cfg))(ours.params)
    # the layers' largest state, as a step metric and beside the logits
    assert 0.1 < float(metrics[GDN_STATE_ABSMAX]) < 100.0
    assert float(aux[GDN_STATE_ABSMAX]) == float(metrics[GDN_STATE_ABSMAX])
    assert set(olmo_hybrid.STEP_METRICS) <= set(metrics)


@pytest.mark.parametrize("wrong", [
    dict(block_norm="input"), dict(qk_norm=False),
    dict(position_embedding="rope"), dict(gdn_neg_eigval=False),
    "gate before the norm", "keys not normalised",
], ids=lambda w: w if isinstance(w, str) else "-".join(
    f"{k}={v}" for k, v in w.items()))
def test_structural_controls_fail_against_the_reference(wrong, monkeypatch):
    """What the comparison must be able to tell from the assumed structure:
    the norm on a block's input, no QK-norm, RoPE left on, beta kept under
    1, the gate applied before the head's norm, keys left at their length.
    Each moves the loss thirty tolerances or more."""
    cfg = _cfg(attn_impl="reference", remat=False)
    params = program_params = program("olmo_hybrid").params
    # before any patch: the reference's answer is kept for the process
    want = float(reference("olmo_hybrid").parts["total"])
    if wrong == "gate before the norm":
        monkeypatch.setattr(delta, "rms_norm", lambda x, w, eps=1e-6: (
            x if w.shape[-1] == 16 and x.ndim == 4
            else rms_norm(x, w, eps)))
    elif wrong == "keys not normalised":
        monkeypatch.setattr(jax.lax, "rsqrt", lambda x: (
            jnp.ones_like(x) if x.ndim == 4 and x.shape[-1] == 1
            else 1.0 / jnp.sqrt(x)))
    elif wrong == dict(qk_norm=False):
        cfg = dataclasses.replace(cfg, qk_norm=False)
        program_params = dict(params, layers=(params["layers"][0], {
            k: v for k, v in params["layers"][1].items()
            if k not in ("q_norm", "k_norm")}))
    else:
        cfg = dataclasses.replace(cfg, **wrong)
    with HIGHEST:
        loss = float(jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg)[0])(
            program_params))
    assert abs(loss - want) / want > 30 * LOSS_TOL, (wrong, loss, want)


def _by_hand(x, lp, cfg, place):
    """One attention layer and its MLP written out, the norm on each
    block's input (``place`` "input") or on what it adds ("output")."""
    def block(x, norm, f):
        if place == "input":
            return x + f(rms_norm(x, norm, cfg.norm_eps))
        return x + rms_norm(f(x), norm, cfg.norm_eps)

    def attention(h):
        b, s, _ = h.shape
        q, k, v = (
            (h @ lp[w]).reshape(b, s, cfg.num_heads, cfg.head_dim)
            for w in ("wq", "wk", "wv"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg.head_dim ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return o.reshape(b, s, -1) @ lp["wo"]

    x = block(x, lp["attn_norm"], attention)
    return block(x, lp["mlp_norm"], lambda h: swiglu(
        h @ lp["w_gate"], h @ lp["w_up"]) @ lp["w_down"])


@pytest.mark.parametrize("place", ["input", "output"])
def test_block_norm_against_a_block_written_out(place):
    """``block_norm="output"`` is ``x + norm(f(x))`` for the mixer and the
    MLP alike; the field left at its default is today's block, ``x +
    f(norm(x))`` (and builds the same program as naming that default)."""
    kw = dict(num_layers=1, position_embedding="nope",
              attn_impl="reference", remat=False)
    cfg = LlamaConfig.tiny(**kw, **({} if place == "input"
                                    else {"block_norm": place}))
    assert cfg.block_norm == place
    params = _drawn(init_params(jax.random.PRNGKey(0), cfg))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 64)),
                    jnp.float32)
    layer_fn = llama._make_layer_fn(cfg, None, None)
    with HIGHEST:
        (got, _), _ = layer_fn((x, llama._zero_aux(cfg)), lp)
        want = _by_hand(x, lp, cfg, place)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if place == "input":
        named = LlamaConfig.tiny(**kw, block_norm="input")
        assert named == cfg
        text = [str(jax.make_jaxpr(lambda p: loss_fn(
            p, {"tokens": TOKENS}, c)[0])(params)) for c in (cfg, named)]
        assert text[0] == text[1] and "gdn" not in text[0]


def test_train_step_reports_the_state_and_names_its_scopes():
    """A train step of the hybrid: ``gdn_state_absmax`` is among the step's
    metrics, the loss falls, and the four ``gdn_*`` scopes — members of
    ``STEP_SCOPES`` — are on the compiled program's ops in the forward,
    the rematerialised and the backward pass, except ``gdn_in``'s
    rematerialised matmul: the checkpoint keeps the projection by name."""
    import re

    from ray_tpu.util.tracing import scope_and_phase

    assert set(GDN_SCOPES) <= set(STEP_SCOPES)
    stepped = train_step_reports("olmo_hybrid")
    assert np.isfinite(float(stepped.metrics[GDN_STATE_ABSMAX]))
    dots = {scope_and_phase(n, STEP_SCOPES) for n in re.findall(
        r'dot\([^\n]*op_name="([^"]*)"', stepped.compiled.as_text())}
    assert ("gdn_in", "forward") in dots and ("gdn_in", "backward") in dots
    assert ("gdn_in", "remat") not in dots


def test_on_a_mesh_the_rule_runs_per_shard_of_the_batch():
    """fsdp=2 x tp=2: the loss and the state's maximum equal one device's
    (the rule inside a manual region, rows over the data axes, the maximum
    taken over the shards), and the mixer's inner width maps to no axis."""
    cfg = _cfg()
    params = _drawn(init_params(jax.random.PRNGKey(0), cfg))
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    with HIGHEST:
        want, want_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg))(params)
        got, got_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg, mesh=mesh))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    np.testing.assert_allclose(got_m[GDN_STATE_ABSMAX],
                               want_m[GDN_STATE_ABSMAX], rtol=1e-5)


def test_on_a_mesh_the_kernels_run_per_shard_of_the_batch():
    """fsdp=2 x tp=2 with heads the kernels fit: inside the manual region
    (``parallel.sharding.batch_shard_map``, as the delta block calls it)
    each shard of the batch runs ``delta_fwd`` / ``delta_bwd`` on its own
    rows (the layouts the wrapper asks for are the shard's own), and the
    outputs, the state's maximum and the five gradients are one device's
    to the last bit."""
    args = _rule_inputs(128, True, seed=6, batch=4, heads=2, dk=32, dv=64)[:5]
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    one, many = delta.shard_rule, batch_shard_map(
        delta.shard_rule, mesh, (4, 4, 4, 3, 3), (4, None), reduce=jax.lax.pmax)
    assert _takes_the_kernels(many, args)
    scalar = lambda rule: (lambda *t: jnp.sum(jnp.square(rule(*t)[0])))
    with HIGHEST:
        (want, want_peak), (o, peak) = (jax.jit(f)(*args)
                                        for f in (one, many))
        want_grads, grads = (jax.jit(jax.grad(scalar(f), argnums=range(5)))(
            *args) for f in (one, many))
    np.testing.assert_array_equal(o, want)
    assert float(peak) == float(want_peak)
    for g, w in zip(grads, want_grads):
        np.testing.assert_array_equal(g, w)
