"""The delta rule with a decay PER KEY CHANNEL (``ops/delta.py``: the
recurrence ``kda_reference``, the chunked XLA form ``kda_xla`` with its
level-wise ``decayed_dots``, the Pallas pair ``kdarule_fwd`` /
``kdarule_bwd`` interpreted), on the CPU at small sizes in float32.  The
model built on it is ``tests/test_kimi_linear.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.blocks import kda
from ray_tpu.ops import delta
from ray_tpu.ops.delta import (
    decayed_dots, delta_chunked, delta_reference, kda_chunked,
    kda_kernels_fit, kda_reference)
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

HIGHEST = jax.default_matmul_precision("highest")


# -- (a) the rule: chunks against the recurrence -------------------------------

def _rule_inputs(seq, strength, seed=0, batch=2, heads=3, dk=16, dv=24,
                 dtype=jnp.float32):
    """q and k as the mixer hands them over (unit length a head, q times
    ``dk ** -0.5``), a log-decay a key CHANNEL of about ``strength`` a
    token (8: a chunk of 64 passes -88 in every channel, several times
    over), beta in (0, 1), and a state that is carried in."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k = unit(f(batch, seq, heads, dk)) * dk ** -0.5, unit(
        f(batch, seq, heads, dk))
    g = -strength * jax.nn.softplus(f(batch, seq, heads, dk))
    return (q.astype(dtype), k.astype(dtype), f(batch, seq, heads, dv).astype(
        dtype), g, jax.nn.sigmoid(f(batch, seq, heads)),
        f(batch, heads, dv, dk))


def _value_and_grads(form, args, weight):
    def scalar(*t):
        out = form(*t)
        o, state = out[:2]
        return jnp.sum(o * weight) + 0.1 * jnp.sum(jnp.square(state)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=range(6), has_aux=True))(*args)
    return out, grads


@pytest.mark.parametrize("seq,chunk,strength", [
    (100, 16, 0.3), (100, 16, 8.0), (128, 64, 0.3), (150, 64, 8.0),
    (24, 64, 2.0)],
    ids=["ragged-16-mild", "ragged-16-past-88", "two-chunks-64-mild",
         "ragged-64-past-88", "shorter-than-a-chunk"])
def test_kda_chunked_equals_the_recurrence(seq, chunk, strength):
    """Values, the state handed on and the gradient of every input — q, k,
    v, the log-decay of every channel, beta and the state carried in —
    against the recurrence a token at a time, at two chunk sizes, on
    sequences no chunk divides, and with decays so strong that a chunk's
    cumulative log-decay passes -88 (where ``exp(-G)`` of a factored chunk
    matrix is infinite in float32): no inf, no NaN, the SAME tolerance —
    float32 against float32 in another order of sums, 2e-5 on values, 2e-4
    of each gradient's scale."""
    args = _rule_inputs(seq, strength)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    with HIGHEST:
        ((o, state, peak, decay), grads), ((want, want_state), want_grads) = (
            _value_and_grads(f, args, weight) for f in (
                functools.partial(kda_chunked, chunk=chunk), kda_reference))
    assert o.shape == want.shape and o.dtype == args[2].dtype
    np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)
    assert float(peak) >= float(jnp.max(jnp.abs(want_state))) - 2e-5
    # the statistic says whether the guarded path was needed
    assert float(decay) <= float(jnp.min(args[3])) <= 0.0
    assert (float(decay) < -88.0) == (strength == 8.0), decay
    for name, g, w in zip("q k v g beta state".split(), grads, want_grads):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.max(jnp.abs(
            w))), rtol=2e-4, err_msg=name)


def test_a_decay_equal_over_the_channels_is_the_scalar_rule():
    """With ``g`` the same in every key channel of a head the rule IS the
    gated delta rule ``ops/delta.py`` has had: the recurrence against
    ``delta_reference``, the chunks against ``delta_chunked``, values and
    the state."""
    q, k, v, g, beta, state = _rule_inputs(96, 0.3, seed=3)
    scalar = g[..., 0]
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    with HIGHEST:
        want, want_state = delta_reference(q, k, v, scalar, beta, state)
        got, got_state = kda_reference(q, k, v, wide, beta, state)
        chunked, chunked_state, _, _ = kda_chunked(q, k, v, wide, beta, state)
        old, old_state, _ = delta_chunked(q, k, v, scalar, beta, state)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got_state, want_state, atol=1e-6)
    np.testing.assert_allclose(chunked, old, atol=2e-5)
    np.testing.assert_allclose(chunked_state, old_state, atol=2e-5)
    # and a decay that DIFFERS over the channels is another function
    with HIGHEST:
        other, _ = kda_reference(q, k, v, g, beta, state)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


@pytest.mark.parametrize("c", [1, 2, 16, 24, 64])
def test_every_pair_parts_at_exactly_one_level(c):
    """The levels' masks tile the strict lower triangle, and at a pair's
    level the row is measured from a token at or before it and the column
    to a token at or after it: both exponents are never positive."""
    levels = delta._levels(c)
    assert len(levels) == (c - 1).bit_length()
    covered = sum(mask.astype(int) for _, _, mask in levels) if levels \
        else np.zeros((c, c), int)
    np.testing.assert_array_equal(covered, np.tri(c, k=-1, dtype=int))
    tok = np.arange(c)
    for row_ref, col_ref, mask in levels:
        assert np.all(row_ref <= tok) and np.all(col_ref >= tok)
        t, j = np.nonzero(mask)
        np.testing.assert_array_equal(row_ref[t], col_ref[j])


def test_decayed_dots_is_the_sum_over_channels_with_its_gradient():
    """Against the sum written out (mild decays, where it can be), forward
    and the written-out backward against autodiff of the plain sum; and at
    decays past -88 a chunk, where the plain FACTORED form is infinite, the
    same function still equals the exact difference form."""
    rng = np.random.default_rng(2)
    x, y = (jnp.asarray(rng.normal(size=(2, 3, 32, 8)), jnp.float32)
            for _ in range(2))

    def plain(x, y, cum):
        apart = cum[..., :, None, :] - cum[..., None, :, :]
        lower = jnp.tri(32, k=-1, dtype=bool)[..., None]
        return jnp.sum(jnp.where(lower, jnp.exp(jnp.where(
            lower, apart, 0.0)), 0.0) * x[..., :, None, :]
            * y[..., None, :, :], -1)

    for strength in (0.2, 6.0):
        cum = jnp.cumsum(-strength * jax.nn.softplus(jnp.asarray(
            rng.normal(size=(2, 3, 32, 8)), jnp.float32)), axis=-2)
        weight = jnp.asarray(rng.normal(size=(2, 3, 32, 32)), jnp.float32)
        with HIGHEST:
            got, grads = jax.value_and_grad(
                lambda *a: jnp.sum(decayed_dots(*a) * weight),
                argnums=(0, 1, 2))(x, y, cum)
            want, want_grads = jax.value_and_grad(
                lambda *a: jnp.sum(plain(*a) * weight),
                argnums=(0, 1, 2))(x, y, cum)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for g, w in zip(grads, want_grads):
            np.testing.assert_allclose(g, w, atol=1e-5 * float(
                jnp.max(jnp.abs(w))), rtol=1e-4)
    assert float(jnp.min(cum)) < -88.0
    assert not np.isfinite(float(jnp.max(jnp.exp(-cum))))   # the factor


def test_kda_in_bfloat16_keeps_its_decays_and_state_in_float32():
    """bfloat16 operands, float32 decays, inverse and state: within 2e-2
    of the float32 recurrence on the same (rounded) inputs, the state
    handed on in float32, at decays past -88 too."""
    for strength in (0.3, 8.0):
        args = _rule_inputs(128, strength, seed=4, dtype=jnp.bfloat16)
        f32 = tuple(a.astype(jnp.float32) for a in args)
        with HIGHEST:
            want, want_state = kda_reference(*f32)
        o, state, peak, _ = jax.jit(kda_chunked)(*args)
        assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) \
            < 2e-2 * scale
        np.testing.assert_allclose(state, want_state, atol=3e-2 * float(
            jnp.max(jnp.abs(want_state))))
        assert np.isfinite(float(peak))


def _takes_the_kernels(form, args):
    """Whether ``form``'s program holds the forward kernel: the dispatch
    as it can be observed."""
    return "kdarule_fwd" in str(jax.make_jaxpr(form)(*args))


PUBLISHED = dict(batch=1, heads=2, dk=128, dv=128)     # Kimi-Linear's heads


@pytest.mark.parametrize("seq,strength,dtype,tol", [
    (200, 0.3, jnp.float32, 2e-5), (128, 4.0, jnp.float32, 2e-5),
    (64, 0.3, jnp.float32, 2e-5), (256, 4.0, jnp.bfloat16, 2e-2),
    (512, 0.3, jnp.float32, 2e-5), (1000, 4.0, jnp.float32, 2e-5),
    (1060, 0.3, jnp.float32, 2e-5), (1024, 4.0, jnp.bfloat16, 2e-2)],
    ids=["ragged-mild", "one-pair-past-88", "one-chunk", "bfloat16-past-88",
         "one-whole-grid-step", "two-grid-steps-ragged-past-88",
         "nine-grid-steps-of-a-pair", "bfloat16-two-grid-steps"])
def test_the_kernels_equal_the_xla_form_and_the_recurrence(seq, strength,
                                                           dtype, tol):
    """The Pallas pair (interpreted here) at the published 128 / 128: on a
    sequence that is no multiple of the chunk, on one pair of chunks whose
    cumulative log-decay passes -88 (-277 at strength 4), on ONE chunk (the
    other half of its pair is padding); values, the state handed on, both
    statistics and the gradient of every input against the XLA form — and,
    in float32, against the recurrence a token at a time.  In bfloat16 the
    two forms round the same operands and differ by the order of their
    sums; nothing but the shapes chooses the form.  Since PR 65 the forward
    kernel hands the backward ONE state a grid step and the backward kernel
    sweeps the step's chunks forward again (``_kda_sweep``): 128 tokens are
    a step of one pair, 512 exactly one step of four, 1000 (padded to 1024)
    two steps of four with a ragged tail, 1060 (to 1152, nine pairs) nine
    steps of one pair — a state enters the first step in every case, so
    each boundary the sweep starts from is on the gradients' path."""
    args = _rule_inputs(seq, strength, seed=5, dtype=dtype, **PUBLISHED)
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    assert _takes_the_kernels(kda_chunked, args)
    assert not _takes_the_kernels(delta.kda_xla, args)
    with HIGHEST:
        (got, grads), (want, want_grads) = (
            _value_and_grads(f, args, weight)
            for f in (kda_chunked, delta.kda_xla))
        exact = _value_and_grads(kda_reference, tuple(
            a.astype(jnp.float32) for a in args), weight)
    f32 = lambda t: np.asarray(t, np.float32)
    scale = float(jnp.max(jnp.abs(f32(want[0]))))
    assert got[0].dtype == args[2].dtype and got[1].dtype == jnp.float32
    np.testing.assert_allclose(f32(got[0]), f32(want[0]), atol=tol * scale)
    np.testing.assert_allclose(got[1], want[1], atol=tol * float(
        jnp.max(jnp.abs(want[1]))))
    np.testing.assert_allclose(got[2], want[2], rtol=max(tol, 1e-4))
    np.testing.assert_allclose(got[3], want[3], rtol=1e-5)
    assert (float(got[3]) < -88.0) == (strength == 4.0)
    for name, g, w, e in zip("q k v g beta state".split(), grads,
                             want_grads, exact[1]):
        assert np.all(np.isfinite(f32(g))), name
        top = float(jnp.max(jnp.abs(f32(w))))
        np.testing.assert_allclose(f32(g), f32(w), atol=10 * tol * top,
                                   err_msg=name)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, e, atol=2e-4 * float(
                jnp.max(jnp.abs(e))), rtol=2e-4, err_msg=name)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[0], exact[0][0], atol=2e-5)


def _overshooting_inputs(seq, dk, dv, chunk, heads=2, spread=0.05, seed=7):
    """The conditioning ``kda_neg_eigval`` worsens: a write strength drawn
    in (1, 2), keys that all but coincide inside every chunk (one direction
    a chunk and head, ``spread`` of noise on it) and a decay so mild that a
    chunk's matrix ``I + tril(beta k k^T decayed)`` holds entries up to 2 —
    at ``beta`` 2 on equal keys without decay its inverse's entries neither
    grow nor shrink along the chunk (the transition's eigenvalue -1)."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    chunks = -(-seq // chunk)
    base = jnp.repeat(f(1, chunks, heads, dk), chunk, axis=1)[:, :seq]
    k = unit(base + spread * f(1, seq, heads, dk))
    q = unit(f(1, seq, heads, dk)) * dk ** -0.5
    g = -0.01 * jax.nn.softplus(f(1, seq, heads, dk))
    beta = 1.0 + jax.nn.sigmoid(f(1, seq, heads))
    return q, k, f(1, seq, heads, dv), g, beta, f(1, heads, dv, dk)


@pytest.mark.parametrize("form,seq,dk,dv,chunk", [
    ("xla", 100, 16, 24, 16), ("xla", 150, 16, 24, 64),
    ("kernels", 200, 128, 128, 64), ("kernels", 1000, 128, 128, 64)],
    ids=["xla-chunks-of-16", "xla-chunks-of-64", "kernels-128",
         "kernels-128-two-grid-steps"])
def test_a_write_strength_over_one_on_keys_that_nearly_coincide(
        form, seq, dk, dv, chunk):
    """``beta`` in (1, 2) — a model with ``kda_neg_eigval`` — on keys that
    nearly coincide inside a chunk: the chunked XLA form at two chunk sizes
    and the Pallas pair (interpreted) against the recurrence a token at a
    time, values, the state handed on and every gradient, at the SAME
    tolerance as ``beta`` under 1 on spread keys (2e-5 on values, 2e-4 of
    each gradient's scale; float32 reads 1e-6 to 5e-6): ``beta`` up to 2
    is in ``unit_lower_inverse``'s range, the inverse is exact, and the
    factor costs float32 nothing that shows."""
    args = _overshooting_inputs(seq, dk, dv, chunk)
    assert 1.0 < float(args[4].min()) and float(args[4].max()) < 2.0
    k = args[1][0, :chunk, 0]
    assert float(jnp.min(k @ k.T)) > 0.9       # one chunk's keys: one line
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    rule = functools.partial(kda_chunked, chunk=chunk)
    assert _takes_the_kernels(rule, args) == (form == "kernels")
    with HIGHEST:
        ((o, state, peak, _), grads), ((want, want_state), want_grads) = (
            _value_and_grads(f, args, weight) for f in (rule, kda_reference))
    np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)
    assert float(peak) >= float(jnp.max(jnp.abs(want_state))) - 2e-5
    for name, g, w in zip("q k v g beta state".split(), grads, want_grads):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.max(jnp.abs(
            w))), rtol=2e-4, err_msg=name)


# -- what the backward kernel rebuilds: the state entering every chunk ----------

def _per_chunk_states(q, k, v, g, beta, h0, pairs):
    """The forward kernel's state loop AS IT STOOD UNTIL PR 65, kept here
    for the comparison alone: a grid step writes the float32 state
    ENTERING each of its chunks, ``(b, heads, s / 64, 128, 128)``.  The
    arguments are ``_kda_fwd_call``'s."""
    from jax.experimental import pallas as pl

    batch, heads, keys, s = q.shape
    sp = delta._kda_plan(q, reverse=False)
    assert sp["pairs"] == pairs

    def body(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref, states_ref, h_scr):
        @pl.when(pl.program_id(2) == 0)
        def _first_step():
            h_scr[...] = h0_ref[0, 0]

        def pair(p, carry):
            at = pl.ds(pl.multiple_of(p * 128, 128), 128)
            t = delta._kda_pair_terms(
                q_ref[0, 0, :, at].T, k_ref[0, 0, :, at].T,
                v_ref[0, 0, :, at].T, g_ref[0, 0, :, at].T,
                beta_ref[0, 0, :, at])
            big, dtype = t["big"], q_ref.dtype
            tb = delta._pair_inverse(t["a"], t["row"], t["col"]).astype(dtype)
            u0 = delta._dot(tb, t["vb"], (1, 0), big)
            w = delta._dot(tb, t["kb"], (1, 0), big).astype(dtype)
            h = h_scr[...]
            for i, rows in enumerate(delta._HALVES):
                states_ref[0, 0, 2 * p + i] = h
                hb = h.astype(dtype)
                u = (u0[rows] - delta._dot(w[rows], hb, (1, 0), big)
                     ).astype(dtype)
                h = delta._column(t["eye"], jnp.exp(t["lasts"][i])) * h \
                    + delta._dot(t["k_end"][rows], u, (0, 0), big)
            h_scr[...] = h
            return carry

        jax.lax.fori_loop(0, pairs, pair, 0)

    return pl.pallas_call(
        body, grid=sp["grid"],
        in_specs=[sp["qk"]] * 4 + [sp["beta"], sp["state"]],
        out_specs=sp["states"],
        out_shape=jax.ShapeDtypeStruct((batch, heads, s // 64, keys, keys),
                                       jnp.float32),
        scratch_shapes=[sp["carry"]], interpret=True)(q, k, v, g, beta, h0)


def _swept_states(k, v, g, beta, entering, tb, pairs):
    """``_kda_sweep`` — the product's, as ``kdarule_bwd`` runs it at the
    head of a grid step — in a call of its own that copies out what it
    left in VMEM: the state entering every chunk, like
    ``_per_chunk_states``' output."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, keys, s = k.shape
    sp = delta._kda_plan(k, reverse=True)      # from the end, as the walk

    def body(k_ref, v_ref, g_ref, beta_ref, entering_ref, tb_ref, out_ref,
             hs_scr, u0_scr, w_scr, u_scr):
        delta._kda_sweep(k_ref, v_ref, g_ref, beta_ref, entering_ref, tb_ref,
                         hs_scr, u0_scr, w_scr, u_scr, pairs)
        out_ref[0, 0] = hs_scr[...]

    of_pair = pltpu.VMEM((pairs, 128, keys), k.dtype)
    return pl.pallas_call(
        body, grid=sp["grid"],
        in_specs=[sp["qk"]] * 3 + [sp["beta"], sp["entering"], sp["tb"]],
        out_specs=sp["states"],
        out_shape=jax.ShapeDtypeStruct((batch, heads, s // 64, keys, keys),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((2 * pairs, keys, keys), jnp.float32),
                        of_pair, of_pair, of_pair],
        interpret=True)(k, v, g, beta, entering, tb)


@pytest.mark.parametrize("seq,pairs,strength,dtype", [
    (128, 1, 4.0, jnp.float32), (512, 4, 0.3, jnp.float32),
    (1024, 4, 4.0, jnp.bfloat16), (1152, 1, 0.3, jnp.bfloat16)],
    ids=["one-pair", "one-step-of-four", "two-steps-of-four-bfloat16",
         "nine-steps-of-one-bfloat16"])
def test_the_backward_sweep_rebuilds_the_forwards_chunk_states_to_the_bit(
        seq, pairs, strength, dtype):
    """``kdarule_fwd`` keeps the state entering each GRID STEP, an eighth
    of the per-chunk states at four pairs a step; ``kdarule_bwd`` makes the
    others again (``_kda_sweep``: the same operands, casts and order as the
    forward's ``_kda_chunk``).  What it makes IS what the forward kernel
    held until PR 65, bit for bit — in float32 and, as the cells run it,
    in bfloat16 —, so the gradient changed by nothing."""
    q, k, v, g, beta, state = _rule_inputs(seq, strength, seed=8, dtype=dtype,
                                           **PUBLISHED)
    by_head = lambda t: jnp.transpose(t, (0, 2, 3, 1))
    args = (by_head(q), by_head(k), by_head(v), by_head(g),
            jnp.transpose(beta, (0, 2, 1))[:, :, None, :],
            jnp.swapaxes(state, -1, -2))
    want = np.asarray(_per_chunk_states(*args, pairs))
    _, entering, tb, *_ = delta._kda_fwd_call(*args, interpret=True)
    assert entering.shape == (1, 2, seq // 128 // pairs, 128, 128)
    assert entering.dtype == jnp.float32 and tb.dtype == dtype
    np.testing.assert_array_equal(entering, want[:, :, ::2 * pairs])
    np.testing.assert_array_equal(
        _swept_states(*args[1:5], entering, tb, pairs), want)
    assert np.any(want[:, :, 1:] != want[:, :, :-1])     # the states move


# -- the kernels' sums of log-decays: one doubling scan along the tokens -------

@functools.lru_cache(maxsize=None)
def _scanned(strength):
    """``_kda_decay_sums`` as the kernels run it (inside a Pallas body,
    interpreted here) on one head's pair of chunks of the kernels' test:
    ``(g (128, 128), {"rows": six, "columns": six}, cum)`` as numpy."""
    from jax.experimental import pallas as pl

    g = _rule_inputs(128, strength, seed=5, **PUBLISHED)[3][0, :, 0]

    def body(g_ref, out_ref):
        rows, cols, cum = delta._kda_decay_sums(
            g_ref[...], delta._iota((128, 128), 0))
        assert rows[0] is None and len(rows) == len(cols) == 6
        for i, t in enumerate(rows[1:] + cols + [cum]):
            out_ref[i] = t

    out = np.asarray(pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(
        (12, 128, 128), jnp.float32), interpret=True)(g))
    return np.asarray(g), {"rows": (None, *out[:5]), "columns": out[5:11]
                           }, out[11]


def _selection(side, level):
    """The 0/1 matrix ``(t, i)`` the kernels multiplied the log-decays by
    before the scan, and the tokens whose sum the level's mask keeps: a row
    ``t`` in an odd block, a column ``j`` in an even one."""
    t, i = np.arange(128)[:, None], np.arange(128)[None, :]
    same, start = (t >> 6) == (i >> 6), (t >> level) << level
    if side == "rows":
        return same & (start < i) & (i <= t), ((t >> level) & 1)[:, 0] == 1
    nxt = np.minimum(start + (1 << level), ((t >> 6) << 6) + 63)
    return same & (t < i) & (i <= nxt), ((t >> level) & 1)[:, 0] == 0


@pytest.mark.parametrize("strength", [0.3, 4.0])
@pytest.mark.parametrize("level", range(6))
@pytest.mark.parametrize("side", ["rows", "columns"])
def test_the_scan_gives_a_levels_sums_of_log_decays(side, level, strength):
    """A level's row sums (after a block's first token up to ``t``) and
    column sums (after ``t`` up to the next block's first token) out of the
    one scan: NOWHERE a positive value, wherever a roll carried it from (no
    ``exp`` passes 1); on the tokens the level's mask keeps each sum within
    1e-6 OF ITSELF of the float64 sum and its ``exp`` within 1e-6 of what
    the 0/1 product of the three-part split gave — at a chunk whose
    cumulative sum passes -88 too."""
    g, sums, _ = _scanned(strength)
    got = sums[side][level]
    if got is None:             # rows at level 0: empty, operands undecayed
        assert (side, level) == ("rows", 0)
        return
    sel, kept = _selection(side, level)
    assert kept.sum() == 64 and np.all(got <= 0.0)
    want = sel.astype(np.float64) @ g.astype(np.float64)
    np.testing.assert_allclose(got[kept], want[kept], rtol=1e-6, atol=0)
    product = np.asarray(delta._sum01(jnp.asarray(sel), delta._split3(
        jnp.asarray(g))))
    np.testing.assert_allclose(np.exp(got[kept]), np.exp(product[kept]),
                               atol=1e-6, rtol=0)


@pytest.mark.parametrize("strength", [0.3, 4.0])
def test_the_scan_gives_a_chunks_cumulative_log_decay(strength):
    """The rows' last stage plus the chunk's first log-decay: each chunk of
    the pair on its own, within 1e-6 of itself of the float64 sum and of the
    0/1 product it replaces, never rising along the tokens."""
    g, _, cum = _scanned(strength)
    chunks = g.astype(np.float64).reshape(2, 64, 128)
    np.testing.assert_allclose(cum, np.cumsum(chunks, axis=1).reshape(
        128, 128), rtol=1e-6, atol=0)
    t, i = np.arange(128)[:, None], np.arange(128)[None, :]
    product = delta._sum01(jnp.asarray(((t >> 6) == (i >> 6)) & (i <= t)),
                           delta._split3(jnp.asarray(g)))
    np.testing.assert_allclose(cum, product, rtol=1e-6, atol=0)
    assert np.all(np.diff(cum.reshape(2, 64, 128), axis=1) <= 0.0)
    assert (cum.min() < -88.0) == (strength == 4.0)


def test_kernels_fit_says_what_the_kernels_were_written_for():
    """The published 128 / 128 at chunks of 64, from shapes alone; every
    other shape runs the XLA form and its program holds no Mosaic call."""
    assert kda_kernels_fit(128, 128, 64)
    assert not any(kda_kernels_fit(*s) for s in (
        (96, 192, 64), (128, 128, 16), (64, 128, 64), (16, 24, 64),
        (128, 256, 64)))
    small = _rule_inputs(128, 0.3)
    assert "pallas_call" not in str(jax.make_jaxpr(kda_chunked)(*small))
    with pytest.raises(ValueError, match="keys and values of 128"):
        delta.kda_kernels(*small)


def test_on_a_mesh_the_kernels_run_per_shard_of_the_batch():
    """fsdp=2 x tp=2 with the published heads: inside the manual region
    (``parallel.sharding.batch_shard_map``, as the block calls it) each
    shard of the batch runs ``kdarule_fwd`` / ``kdarule_bwd`` on its own
    rows, and the outputs, both statistics and the five gradients are one
    device's to the last bit."""
    from ray_tpu.parallel.sharding import batch_shard_map

    args = _rule_inputs(128, 2.0, seed=6, batch=4, heads=1, dk=128,
                        dv=128)[:5]
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    one, many = kda.shard_rule, batch_shard_map(
        kda.shard_rule, mesh, (4, 4, 4, 4, 3), (4, None, None),
        reduce=jax.lax.pmax)

    def both(rule):
        def scalar(*t):
            o, peak, low = rule(*t)
            return jnp.sum(jnp.sin(o)), (o, peak, low)
        return jax.jit(jax.value_and_grad(scalar, argnums=range(5),
                                          has_aux=True))(*args)

    ((_, want), want_grads), ((_, got), grads) = both(one), both(many)
    for a, b in zip((*got, *grads), (*want, *want_grads)):
        np.testing.assert_array_equal(a, b)
