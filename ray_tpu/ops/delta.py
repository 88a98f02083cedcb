"""Linear-attention mixer ops: the GATED DELTA RULE (Yang, Kautz &
Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464; its chunked
form is Yang et al. 2024, arXiv:2406.06484; ``beta`` up to 2, an eigenvalue
of the transition in (-1, 1), is Grazzi et al. 2024, arXiv:2411.12537).

Per head, with a scalar decay ``exp(g_t)`` and a scalar write strength
``beta_t`` a token, the layer keeps a state ``S`` (value size x key size)

    S_t = exp(g_t) S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

— the state forgets what it held along ``k_t`` before it writes ``v_t``
there (with ``|k_t| = 1``: the transition's eigenvalue along ``k_t`` is
``1 - beta_t``).  Unlike ``ops/ssm.py``'s scalar-decay recurrence the
transition is a MATRIX, so a chunk is no masked matmul: its tokens depend
on each other through the state.

``delta_reference`` is the recurrence itself, a token at a time in float32:
what the tests hold the chunked form to.  ``delta_chunked`` computes it in
chunks of ``chunk`` tokens (the WY / UT-transform form).  With ``G`` the
cumulative log-decay inside a chunk, ``H = S^T`` the state entering it and

    A[t, j] = beta_t exp(G_t - G_j) (k_t . k_j)   for j < t, else 0
    T = (I + A)^-1
    U = T (beta v) - T (beta exp(G) k) H          the chunk's "new values"

the chunk's outputs and the state it leaves are

    o = (exp(G) q) H + (exp(G_t - G_j) (q_t . k_j))_(j <= t) U
    H' = exp(G_last) H + (exp(G_last - G_j) k_j)^T U

Two forms of ONE algorithm, chosen by what a call's shapes show
(``kernels_fit``): where chunks are 64 tokens, the keys fill whole sublane
tiles up to one lane block and the values whole half-blocks of lanes up to
two (the published 96 and 192) — ``delta_kernels``, two Pallas kernels
under a ``custom_vjp`` (interpreted off the chip, so the tests run the
same code); elsewhere ``delta_xla``, the same sums as plain XLA: everything
but ``H`` for all chunks at once, ONE state a head carried across chunks by
``lax.scan`` (not unrolled), the per-chunk matrices and the entering states
arrays in memory, autodiff but for the inverse.  ``delta_xla`` is also the
tests' second oracle.  Both take a state that enters.  ``T`` is the inverse
of a unit lower triangular matrix of ``chunk`` rows
(``unit_lower_inverse``): the inverses of its diagonal blocks of two rows,
merged by doubling, ten products of ``chunk`` rows at 64 (in the kernels:
of a pair's 128) in place of a substitution of ``chunk`` dependent steps;
its backward pass is written out (``dA = -T^T dT T^T``).

The kernels (``delta_fwd``, ``delta_bwd``): grid ``(batch, head, step)``,
a step being a few PAIRS of chunks worked through in turn (written out, not
looped: ``_each_pair``); the step axis is sequential and the head's
``(keys, values)`` float32 state rides in a VMEM scratch from one chunk to
the next (backward: its gradient, chunks in reverse).  A pair's two chunks
sit on the block diagonal of ONE ``(128, 128)`` matrix (a ``(64, 64)``
float32 tile fills half the lanes; the zeros add nothing, so it stays
exact): ``A``, ``T``, ``T (beta v)``, ``T (beta
decay k)``, the masked ``q k^T`` and the state never leave VMEM.  q, k, v
and o are read and written a head at a time with the SEQUENCE as the minor
dimension, ``(batch, heads, d, s)`` — how XLA lays the mixer's arrays out
round its convolution anyway, so nothing is transposed in memory round a
call — and a pair's ``(d, 128)`` tile is stood up in VMEM.  ``delta_fwd``
also writes what ``delta_bwd`` needs beside the inputs: the state ENTERING
each chunk (float32, 142 MB a layer at the published sizes) and each
pair's inverse as the products read it (``q.dtype``, 31 MB); under a layer
checkpoint the forward kernel runs again in the backward pass and neither
reaches the checkpoint's stack (true of THIS rule's pair only since PR 65:
the per-channel rule's, below, keeps one state a grid step by name).  With
the states saved ``delta_bwd`` remakes a pair's ``u`` for both chunks at
once and only the state's gradient walks; the inverse's gradient needs no
float32 product of 128 rows (``dA = -(T^T du0) (T vb)^T - (T^T dw) (T
kb)^T``).  XLA makes a chunk's cumulative log-decays and differentiates them.

Precision: log-decays, their cumulative sums, every ``exp``, ``A``, the
inverse and the carried state are float32 (the inverse's ten products at
``Precision.HIGHEST``, six passes of the MXU each, all six cross terms of
the operands' bfloat16 parts: ``_pair_inverse`` has what was tried in their
place); the operands of the big products (``k k^T``, ``q k^T``, ``T`` times
values and keys, everything times the state) are in ``q.dtype`` with
float32 accumulation.  ``state_absmax`` — the largest
``|S|`` at a chunk's end — is the first number to read when a comparison
with the recurrence drifts.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.ssm import exp_where, pad_to_multiple

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_CHUNK = 64           # the kernels' chunk: two side by side fill the lanes
_PAIR = 2 * _CHUNK
_HALVES = (slice(0, _CHUNK), slice(_CHUNK, _PAIR))   # a pair's two chunks
# Pairs of chunks a grid step works through, at most: fewer, longer steps.
_STEP_PAIRS = 4


def delta_reference(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                    beta: jax.Array, state: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence above, a token at a time in float32.

    ``q``, ``k`` ``(batch, s, heads, key_dim)``; ``v (batch, s, heads,
    value_dim)``; ``g`` (log-decay, at most 0) and ``beta`` ``(batch, s,
    heads)``; ``state (batch, heads, value_dim, key_dim)`` enters (zeros
    where None).  Returns ``(o (batch, s, heads, value_dim) float32, the
    state after the last token)``."""
    batch, _, heads, dk = q.shape
    if state is None:
        state = jnp.zeros((batch, heads, v.shape[-1], dk), _F32)

    def token(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        held = jnp.einsum("zhvk,zhk->zhv", s, k_t, precision=_HIGHEST)
        s = jnp.exp(g_t)[..., None, None] * (
            s - beta_t[..., None, None] * held[..., :, None]
            * k_t[..., None, :]) + beta_t[..., None, None] * (
                v_t[..., :, None] * k_t[..., None, :])
        return s, jnp.einsum("zhvk,zhk->zhv", s, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(
        token, state.astype(_F32),
        tuple(jnp.moveaxis(t.astype(_F32), 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _doubled_inverse(a):
    n = a.shape[-1]
    row = jnp.arange(n)

    def together(rows):   # entries of one diagonal block of ``rows`` rows
        return row[:, None] // rows == row[None, :] // rows

    # blocks of two rows: (I + [[0, 0], [r, 0]])^-1 = I - [[0, 0], [r, 0]]
    inv, rows = jnp.eye(n, dtype=_F32) - jnp.where(together(2), a, 0.0), 2
    while rows < n:
        across = jnp.where(together(2 * rows) & ~together(rows), a, 0.0)
        inv = inv - jnp.matmul(
            jnp.matmul(inv, across, precision=_HIGHEST), inv,
            precision=_HIGHEST)
        rows *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a (..., n, n)`` STRICTLY lower triangular,
    float32.  By doubling: ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
    P^-1, Q^-1]]``, from blocks of two rows up, every level on the WHOLE
    matrix — with ``D`` the inverse of the diagonal blocks so far and ``R``
    the entries that pair them, the next is ``D - D R D`` (the zeros add
    nothing, so the sums are the block formula's) — ``2 log2(n / 2)``
    products of ``n`` rows in place of a substitution of ``n`` dependent
    steps.  Every factor is the inverse of a sub-chunk's own matrix, which
    the rule keeps bounded.  (The finite series ``sum (-a)^i = (I - a)(I +
    a^2)(I + a^4)...`` is exact on paper, but its terms grow like ``C(n, i)
    |a|^i`` before they cancel: at 64 rows, with keys that lean one way
    (after a SiLU they do) and ``beta`` up to 2, float32 loses the result,
    a per-token loss apart by 4e-3 where 1e-5 is due:
    ``tests/test_delta.py``.  And blocks cut OUT of the matrix cost memory
    on the chip: a ``(16, 16)`` float32 block is laid out 128 lanes wide,
    eight times its size.)"""
    return _doubled_inverse(a)


def _inverse_fwd(a):
    inv = unit_lower_inverse(a)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    # d (I + a)^-1 = -T da T: the gradient to a is -T^T dT T^T, and only
    # the entries below the diagonal are a's
    t = jnp.swapaxes(inv, -1, -2)
    da = -jnp.matmul(jnp.matmul(t, d_inv, precision=_HIGHEST), t,
                     precision=_HIGHEST)
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def delta_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, state: Optional[jax.Array] = None, *,
                  chunk: int = 64
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``delta_reference`` in chunks of ``chunk`` tokens (the module's
    docstring has the algebra); arguments as there, ``g`` and ``beta``
    float32.  Returns ``(o`` like ``v``, the state after the last token
    float32, ``state_absmax``: the largest ``|S|`` at any chunk's end, no
    gradient through it``)``.  A sequence that is no multiple of the chunk
    is padded with tokens of ``beta`` 0 and ``g`` 0, which leave the state
    as it is.

    The Pallas kernels where the shapes tile the chip (``kernels_fit``),
    a state that enters included; the XLA form elsewhere: one algorithm,
    the same values to rounding."""
    form = delta_kernels if kernels_fit(
        q.shape[3], v.shape[3], min(chunk, q.shape[1])) else delta_xla
    return form(q, k, v, g, beta, state, chunk=chunk)


def kernels_fit(key_dim: int, value_dim: int, chunk: int) -> bool:
    """Whether a call's shapes tile the chip for ``delta_kernels``: chunks
    of 64 tokens, so that two side by side fill the 128 lanes; keys that
    fill whole sublane tiles of either dtype up to ONE lane block (a
    multiple of 16 up to 128: the published 96); values in whole
    half-blocks of lanes up to two blocks (64, 128, 192 — the published —,
    256).  Any number of heads, any batch, any sequence of a chunk or more
    (padded to a multiple of 128)."""
    return (chunk == _CHUNK and 16 <= key_dim <= _LANES
            and key_dim % 16 == 0 and 64 <= value_dim <= 2 * _LANES
            and value_dim % 64 == 0)


def delta_xla(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, state: Optional[jax.Array] = None, *,
              chunk: int = 64) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``delta_chunked`` as plain XLA, for any shapes: the per-chunk
    matrices and the entering states are arrays in memory, ONE ``lax.scan``
    carries the state across chunks, and autodiff writes the backward pass
    but for the inverse's."""
    batch, s, heads, dk = q.shape
    dv, dtype = v.shape[-1], q.dtype
    c = min(chunk, s)
    q, k, v, g, beta = pad_to_multiple(c, q, k, v, g, beta)
    n = q.shape[1] // c

    def chunks(t):   # (z, s, h, ...) -> (z, chunk, h, token, ...)
        return jnp.moveaxis(t.reshape(batch, n, c, *t.shape[2:]), 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)              # (z, c, h, t, d)
    g, beta = chunks(g.astype(_F32)), chunks(beta.astype(_F32))  # (z, c, h, t)
    cum = jnp.cumsum(g, axis=-1)         # log-decay from the chunk's start
    apart = cum[..., :, None] - cum[..., None, :]          # G_t - G_j
    tokens = jnp.arange(c)
    before = tokens[None, :] < tokens[:, None]             # j < t

    kk = jnp.einsum("zchtk,zchjk->zchtj", k, k, preferred_element_type=_F32)
    a = beta[..., :, None] * exp_where(before, apart) * kk
    inv = unit_lower_inverse(a).astype(dtype)
    grown = jnp.exp(cum)[..., None]                        # exp(G_t)
    u0 = jnp.einsum("zchtj,zchjv->zchtv", inv,
                    (beta[..., None] * v.astype(_F32)).astype(dtype),
                    preferred_element_type=_F32)
    w = jnp.einsum("zchtj,zchjk->zchtk", inv,
                   (beta[..., None] * grown * k.astype(_F32)).astype(dtype),
                   preferred_element_type=_F32).astype(dtype)
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None]       # exp(G_last - G_j)
    k_end = (k.astype(_F32) * to_end).astype(dtype)
    whole = jnp.exp(cum[..., -1])                          # (z, c, h)

    def one_chunk(carry, at):
        h, peak = carry                                    # (z, h, k, v) f32
        u0_c, w_c, k_end_c, whole_c = at
        u = (u0_c - jnp.einsum("zhtk,zhkv->zhtv", w_c, h.astype(dtype),
                               preferred_element_type=_F32)).astype(dtype)
        left = whole_c[..., None, None] * h + jnp.einsum(
            "zhtk,zhtv->zhkv", k_end_c, u, preferred_element_type=_F32)
        peak = jnp.maximum(peak, jnp.max(jnp.abs(
            jax.lax.stop_gradient(left))))
        return (left, peak), (h.astype(dtype), u)

    h0 = (jnp.zeros((batch, heads, dk, dv), _F32) if state is None
          else jnp.swapaxes(state.astype(_F32), -1, -2))
    (h_last, peak), (entering, u) = jax.lax.scan(
        one_chunk, (h0, jnp.zeros((), _F32)),
        tuple(jnp.moveaxis(t, 1, 0) for t in (u0, w, k_end, whole)))
    entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)

    qk = jnp.einsum("zchtk,zchjk->zchtj", q, k, preferred_element_type=_F32)
    m = (exp_where(~before.T, apart) * qk).astype(dtype)  # j <= t
    o = jnp.einsum("zchtj,zchjv->zchtv", m, u, preferred_element_type=_F32)
    o = o + jnp.einsum("zchtk,zchkv->zchtv",
                       (q.astype(_F32) * grown).astype(dtype), entering,
                       preferred_element_type=_F32)
    o = jnp.moveaxis(o, 2, 3).reshape(batch, n * c, heads, dv)[:, :s]
    return o.astype(v.dtype), jnp.swapaxes(h_last, -1, -2), peak


# ------------------------------------------------------- the Pallas form
#
# A grid step takes a few PAIRS of chunks of one head in turn.  A pair is
# 128 tokens: its two chunks' (64, 64) matrices sit on the block diagonal
# of ONE (128, 128) matrix, which fills the lanes (a (64, 64) float32 tile
# fills half of them) and halves the MXU's weight loads; the zeros off the
# blocks add nothing, so every sum is the chunk's own.  A token's scalars
# (the cumulative log-decay of its chunk, beta) come as ROWS ``(2, 128)``
# — a ``(tokens, 1)`` array would be laid out 128 lanes wide in memory —
# and are stood up as columns through the diagonal; q, k, v come with the
# tokens in the lanes too, ``(d, 128)``, and are transposed in VMEM.


def _dot(a, b, contract, precision=None):
    """``a`` x ``b`` contracting axis ``contract[0]`` of ``a`` with axis
    ``contract[1]`` of ``b``, float32 out."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _each_pair(pairs, body, init):
    """``body(p, carry)`` for a grid step's pairs in turn, WRITTEN OUT: the
    step's few pairs one after the other in one block of code, no loop.  A
    pair is two chains of products that wait on each other — the inverse's
    ten, then the state's walk through its two chunks — and a product of
    128 rows answers after some 130 cycles: inside a loop nothing of the
    next pair starts before this one ends (and each turn of the loop stalls
    about 0.5 us on the chip beside what its schedule says), written out
    the next pair's decays, products and inverse run beside this pair's
    walk.  The sums are the loop's, in the loop's order: results to the
    bit."""
    return jax.lax.fori_loop(0, pairs, body, init, unroll=True)


def _halves(parts):
    """Two ``(64, n)`` results of a pair's chunks, one under the other."""
    return jnp.concatenate(parts, axis=0)


def _pair_inverse(a, row, col):
    """``unit_lower_inverse`` of a pair's ``a (128, 128)`` — zero but
    below the diagonal of its two blocks —: the same doubling, its five
    levels on the whole matrix, float32 at full precision.  Ten float32
    products at ``Precision.HIGHEST``, which the chip's compiler makes of
    six passes of the MXU each (the operands' three bfloat16 parts cut off
    by masks, the cross terms ``hi.hi, hi.mid, mid.hi, hi.lo, lo.hi,
    mid.mid``, float32 accumulation): sixty of a pair's about eighty.  They
    are a CHAIN, each waiting on the one before, and that is what they
    cost: PR 80 wrote them as three passes each (the two chunks are the
    blocks of a block-diagonal operand, so a second part fits where the
    other chunk's zeros are; the same six terms) and the inverse alone read
    1.99 us a pair either way, the forward kernels 3.68 for 3.67 — a lane
    roll that packs an operand answers after 114 cycles, a product after
    131, and the packing's selects and roundings cost the vector unit what
    the passes had cost the MXU (``PERF.md`` section 6, PR 80).  What pays
    is work of ANOTHER pair beside the chain: ``_each_pair``."""
    def together(rows):   # rows a power of two: one diagonal block of them
        shift = rows.bit_length() - 1
        return (row >> shift) == (col >> shift)

    inv = jnp.where(row == col, 1.0, 0.0) - jnp.where(together(2), a, 0.0)
    rows = 2
    while rows < _CHUNK:
        across = jnp.where(together(2 * rows) & ~together(rows), a, 0.0)
        inv = inv - _dot(_dot(inv, across, (1, 0), _HIGHEST), inv, (1, 0),
                         _HIGHEST)
        rows *= 2
    return inv


def _pair_terms(q, k, v, sc):
    """What forward and backward both make of one pair from its ``q``,
    ``k`` ``(128, keys)``, ``v (128, values)`` and scalar rows ``sc (2,
    128)``, before anything reads the state."""
    dtype = q.dtype
    big = _HIGHEST if dtype == _F32 else None   # operands are q.dtype's
    row, col = _iota((_PAIR, _PAIR), 0), _iota((_PAIR, _PAIR), 1)
    eye = row == col
    same = (row >= _CHUNK) == (col >= _CHUNK)

    def column(r):   # (1, 128) -> (128, 1)
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    cum_r, beta_r = sc[0:1, :], sc[1:2, :]
    cum, beta = column(cum_r), column(beta_r)
    # exp(G_t - G_j): right where j <= t in one chunk, capped at 1 elsewhere
    decay = jnp.exp(jnp.minimum(cum - cum_r, 0.0))
    lane, tok = _iota((1, _PAIR), 1), _iota((_PAIR, 1), 0)
    lasts = [jnp.sum(jnp.where(lane == i * _CHUNK + _CHUNK - 1, cum_r, 0.0),
                     axis=1, keepdims=True) for i in range(2)]   # (1, 1)
    grown = jnp.exp(cum)                                         # exp(G_t)
    to_end = jnp.exp(jnp.where(tok < _CHUNK, lasts[0], lasts[1]) - cum)
    kf, qf = k.astype(_F32), q.astype(_F32)
    below, upto = same & (col < row), same & (col <= row)
    kk = _dot(k, k, (1, 1), big)
    qk = jnp.where(upto, decay * _dot(q, k, (1, 1), big), 0.0)
    return dict(
        big=big, row=row, col=col, eye=eye, below=below, upto=upto, tok=tok,
        beta=beta, decay=decay, grown=grown, to_end=to_end,
        wholes=[jnp.exp(v_) for v_ in lasts], kf=kf, qf=qf, kk=kk, qk=qk,
        a=jnp.where(below, beta * decay * kk, 0.0),
        vb=(beta * v.astype(_F32)).astype(dtype),
        kb=(beta * grown * kf).astype(dtype),
        qg=(grown * qf).astype(dtype), k_end=(to_end * kf).astype(dtype))


def _fwd_kernel(q_ref, k_ref, v_ref, sc_ref, h0_ref,
                o_ref, states_ref, tb_ref, hlast_ref, peak_ref, h_scr, *,
                pairs):
    """One head's chunks in order, the state in ``h_scr`` ``(keys,
    values)`` float32.  Beside ``o`` it writes what the backward kernel
    needs and cannot cheaply remake: the state ENTERING every chunk and
    the pair's inverse as the products read it (``q.dtype``)."""
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_step():
        h_scr[...] = h0_ref[0, 0]
        peak_ref[...] = jnp.zeros_like(peak_ref)

    def pair(p, peak):
        at = pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)
        t = _pair_terms(q_ref[0, 0, :, at].T, k_ref[0, 0, :, at].T,
                        v_ref[0, 0, :, at].T, sc_ref[0, 0, :, at])
        big = t["big"]
        tb = _pair_inverse(t["a"], t["row"], t["col"]).astype(dtype)
        tb_ref[0, 0, at, :] = tb
        u0 = _dot(tb, t["vb"], (1, 0), big)
        w = _dot(tb, t["kb"], (1, 0), big).astype(dtype)
        h, us, from_state = h_scr[...], [], []
        for i, rows in enumerate(_HALVES):
            states_ref[0, 0, 2 * p + i] = h
            hb = h.astype(dtype)
            u = (u0[rows] - _dot(w[rows], hb, (1, 0), big)).astype(dtype)
            from_state.append(_dot(t["qg"][rows], hb, (1, 0), big))
            h = t["wholes"][i] * h + _dot(t["k_end"][rows], u, (0, 0), big)
            peak = jnp.maximum(peak, jnp.max(jnp.abs(h)))
            us.append(u)
        h_scr[...] = h
        o = _dot(t["qk"].astype(dtype), _halves(us), (1, 0), big) + _halves(
            from_state)
        o_ref[0, 0, :, at] = o.astype(o_ref.dtype).T
        return peak

    peak = _each_pair(pairs, pair, jnp.zeros((), _F32))
    peak_ref[...] = jnp.maximum(peak_ref[...], peak)
    hlast_ref[0, 0] = h_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, sc_ref, states_ref, tb_ref, do_ref,
                dhl_ref, dq_ref, dk_ref, dv_ref, dsc_ref, dh0_ref, dh_scr, *,
                pairs):
    """One head's chunks from the last to the first (the index maps turn
    the grid round); ``dh_scr`` is the gradient to the state LEAVING the
    chunk.  With the entering states saved nothing but that gradient walks:
    a pair's ``u`` is remade for both chunks at once.  The inverse's
    gradient needs no product of 128-row float32 matrices: with ``dT = du0
    vb^T + dw kb^T``, ``dA = -T^T dT T^T = -(T^T du0) (T vb)^T - (T^T dw)
    (T kb)^T``, two big products of what the pair has anyway.  The
    gradient to a token's cumulative log-decay is row sums less column
    sums of ONE float32 matrix (they are summed again over the chunk
    outside and would not survive two roundings)."""
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_step():
        dh_scr[...] = dhl_ref[0, 0]

    def pair(j, carry):
        p = pairs - 1 - j
        at = pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)
        q, k, v = (ref[0, 0, :, at].T for ref in (q_ref, k_ref, v_ref))
        t = _pair_terms(q, k, v, sc_ref[0, 0, :, at])
        big, tok, beta, grown = t["big"], t["tok"], t["beta"], t["grown"]
        tb, do = tb_ref[0, 0, at, :], do_ref[0, 0, :, at].T
        hs = [states_ref[0, 0, 2 * p + i] for i in range(2)]
        hbs = [h.astype(dtype) for h in hs]

        u0 = _dot(tb, t["vb"], (1, 0), big)
        w = _dot(tb, t["kb"], (1, 0), big).astype(dtype)
        u = (u0 - _halves([_dot(w[r], hb, (1, 0), big)
                           for r, hb in zip(_HALVES, hbs)])).astype(dtype)
        # o = (masked q k^T) u + (exp(G) q) H
        dm = jnp.where(t["upto"], _dot(do, u, (1, 1), big), 0.0)
        du_o = _dot(t["qk"].astype(dtype), do, (0, 0), big)
        dqg = _halves([_dot(do[r], hb, (1, 1), big)
                       for r, hb in zip(_HALVES, hbs)])

        dh, walked = dh_scr[...], {}
        for i in (1, 0):   # du, dw, d k_end, and what <dH', H> gives G_last
            r, hb, whole = _HALVES[i], hbs[i], t["wholes"][i]
            dhb = dh.astype(dtype)
            du = du_o[r] + _dot(t["k_end"][r], dhb, (1, 0), big)
            dub = du.astype(dtype)
            walked[i] = (du, -_dot(dub, hb, (1, 1), big),
                         _dot(u[r], dhb, (1, 1), big),
                         whole * jnp.sum(dh * hs[i]))
            dh = (whole * dh + _dot(t["qg"][r], do[r], (0, 0), big)
                  - _dot(w[r], dub, (0, 0), big))
        dh_scr[...] = dh
        du, dw, dk_end = (_halves([walked[i][n] for i in range(2)])
                          for n in range(3))

        dvb = _dot(tb, du.astype(dtype), (0, 0), big)      # T^T du0
        dkb = _dot(tb, dw.astype(dtype), (0, 0), big)      # T^T dw
        da = -(_dot(dvb.astype(dtype), u0.astype(dtype), (1, 1), big)
               + _dot(dkb.astype(dtype), w, (1, 1), big))
        x = jnp.where(t["below"], da * t["decay"], 0.0)
        dkk = beta * x
        both = dkk * t["kk"] + dm * t["qk"]                # dA o A + dM o M
        by_kb = jnp.sum(dkb * t["kf"], axis=1, keepdims=True) * grown
        to_ends = t["to_end"] * jnp.sum(dk_end * t["kf"], axis=1,
                                        keepdims=True)
        dcum = (jnp.sum(both, axis=1, keepdims=True) + beta * by_kb
                + grown * jnp.sum(dqg * t["qf"], axis=1, keepdims=True)
                - to_ends)
        for i in range(2):   # what a chunk's last token collects
            mine = (tok >= i * _CHUNK) & (tok < (i + 1) * _CHUNK)
            dcum = dcum + jnp.where(
                tok == i * _CHUNK + _CHUNK - 1,
                jnp.sum(jnp.where(mine, to_ends, 0.0)) + walked[i][3], 0.0)
        dbeta = (jnp.sum(x * t["kk"], axis=1, keepdims=True) + by_kb
                 + jnp.sum(dvb * v.astype(_F32), axis=1, keepdims=True))

        def row(c):   # (128, 1) -> (1, 128)
            return jnp.sum(jnp.where(t["eye"], c, 0.0), axis=0,
                           keepdims=True)

        dsc_ref[0, 0, 0:1, at] = row(dcum) - jnp.sum(both, axis=0,
                                                     keepdims=True)
        dsc_ref[0, 0, 1:2, at] = row(dbeta)
        dqk = (dm * t["decay"]).astype(dtype)
        dkkb = dkk.astype(dtype)
        dq_ref[0, 0, :, at] = (_dot(dqk, k, (1, 0), big)
                               + grown * dqg).astype(dq_ref.dtype).T
        dk_ref[0, 0, :, at] = (
            _dot(dkkb, k, (1, 0), big) + _dot(dkkb, k, (0, 0), big)
            + _dot(dqk, q, (0, 0), big) + beta * grown * dkb
            + t["to_end"] * dk_end).astype(dk_ref.dtype).T
        dv_ref[0, 0, :, at] = (beta * dvb).astype(dv_ref.dtype).T
        return carry

    _each_pair(pairs, pair, 0)
    dh0_ref[0, 0] = dh_scr[...]


def _compiler_params(interpret):
    if interpret:
        return None
    # Heads are independent; a head's chunks carry its state in order.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _plan(q, v, reverse):
    """What the two calls share: the grid ``(batch, head, step)``, a step
    being as many pairs of chunks as ``_STEP_PAIRS`` allows and the
    sequence divides, taken in order or, ``reverse``, from the end; the
    BlockSpecs; the VMEM scratch that carries one state."""
    batch, heads, keys, s = q.shape
    values = v.shape[2]
    pairs = _STEP_PAIRS
    while (s // _PAIR) % pairs:
        pairs //= 2
    steps, tokens = s // _PAIR // pairs, pairs * _PAIR

    def spec(block, index):
        return pl.BlockSpec(block, lambda b_, h_, c_: index(
            b_, h_, steps - 1 - c_ if reverse else c_))

    return dict(
        grid=(batch, heads, steps), pairs=pairs,
        carry=pltpu.VMEM((keys, values), _F32),
        qk=spec((1, 1, keys, tokens), lambda b_, h_, c_: (b_, h_, 0, c_)),
        v=spec((1, 1, values, tokens), lambda b_, h_, c_: (b_, h_, 0, c_)),
        sc=spec((1, 1, 2, tokens), lambda b_, h_, c_: (b_, h_, 0, c_)),
        tb=spec((1, 1, tokens, _PAIR), lambda b_, h_, c_: (b_, h_, c_, 0)),
        states=spec((1, 1, 2 * pairs, keys, values),
                    lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        state=spec((1, 1, keys, values), lambda b_, h_, c_: (b_, h_, 0, 0)),
        peak=spec((1, 1, 8, _LANES), lambda b_, h_, c_: (b_, h_, 0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(q, k, v, sc, h0, *, interpret):
    """``q``, ``k`` ``(b, heads, keys, s)``, ``v (b, heads, values, s)``,
    ``sc (b, heads, 2, s)`` float32 (a token's cumulative log-decay inside
    its chunk of 64, its beta), ``h0 (b, heads, keys, values)`` float32;
    ``s`` a multiple of 128.  Returns ``o`` like ``v``, the state entering
    every chunk ``(b, heads, s / 64, keys, values)`` float32, every pair's
    inverse ``(b, heads, s, 128)`` in ``q.dtype``, the last state like
    ``h0`` and its largest entry at any chunk's end ``(b, heads, 8,
    128)``."""
    sp = _plan(q, v, reverse=False)
    batch, heads, keys, s = q.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, pairs=sp["pairs"]),
        grid=sp["grid"],
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["sc"], sp["state"]],
        out_specs=[sp["v"], sp["states"], sp["tb"], sp["state"],
                   sp["peak"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, heads, s // _CHUNK, keys, v.shape[2]), _F32),
                   jax.ShapeDtypeStruct((batch, heads, s, _PAIR), q.dtype),
                   jax.ShapeDtypeStruct(h0.shape, _F32),
                   jax.ShapeDtypeStruct((batch, heads, 8, _LANES), _F32)],
        scratch_shapes=[sp["carry"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="delta_fwd",
    )(q, k, v, sc, h0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(q, k, v, sc, states, tb, do, dh_last, *, interpret):
    """Gradients to ``q``, ``k``, ``v``, ``sc`` and ``h0``, each like its
    argument."""
    sp = _plan(q, v, reverse=True)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, pairs=sp["pairs"]),
        grid=sp["grid"],
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["sc"], sp["states"],
                  sp["tb"], sp["v"], sp["state"]],
        out_specs=[sp["qk"], sp["qk"], sp["v"], sp["sc"], sp["state"]],
        out_shape=[like(q), like(k), like(v), like(sc), like(dh_last)],
        scratch_shapes=[sp["carry"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="delta_bwd",
    )(q, k, v, sc, states, tb, do, dh_last)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, sc, h0, interpret):
    o, _, _, h_last, peak = _fwd_call(q, k, v, sc, h0, interpret=interpret)
    return o, h_last, peak


def _rule_fwd(q, k, v, sc, h0, interpret):
    o, states, tb, h_last, peak = _fwd_call(q, k, v, sc, h0,
                                            interpret=interpret)
    return (o, h_last, peak), (q, k, v, sc, states, tb)


def _rule_bwd(interpret, res, cts):
    do, dh_last, _ = cts
    return _bwd_call(*res, do, dh_last, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _tokens_minor(t):
    """``t (batch, s, ...)`` held to the layout XLA gives the mixer's
    arrays when it is left alone: the sequence as the minor dimension, as
    its convolution along the sequence wants it.  A custom call's operands
    have their layouts fixed, and XLA would rather lay out everything UP
    to the projections to suit them (which turns the transposes round the
    call into bitcasts and puts the batch of ONE between the two tiled
    dimensions: tiles of one row, the convolution at a fifth of its
    speed) than transpose once."""
    order = (0, *range(2, t.ndim), 1)
    return with_layout_constraint(t, Layout(major_to_minor=order))


def delta_kernels(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, state: Optional[jax.Array] = None, *,
                  chunk: int = 64) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``delta_chunked`` through the Pallas kernels (chunks of 64;
    compiled on the TPU, interpreted elsewhere).  XLA pads the sequence to
    whole pairs of chunks, makes each chunk's cumulative log-decays and
    differentiates both; the kernels read q, k, v a head at a time with
    the sequence as the minor dimension, ``(b, heads, d, s)``, which is
    how XLA lays the mixer's arrays out anyway (``_tokens_minor``), so
    nothing is copied round a call, and stand a pair's tile up in VMEM."""
    batch, s, heads, dk = q.shape
    if min(chunk, s) != _CHUNK:
        raise ValueError(f"delta_kernels takes chunks of {_CHUNK}, got "
                         f"{min(chunk, s)}")
    q, k, v, g, beta = pad_to_multiple(
        _PAIR, *(_tokens_minor(t) for t in (
            q, k, v, g.astype(_F32), beta.astype(_F32))))
    padded = q.shape[1]
    cum = jnp.cumsum(g.reshape(batch, padded // _CHUNK, _CHUNK, heads),
                     axis=2).reshape(batch, padded, heads)
    sc = jnp.transpose(jnp.stack([cum, beta], axis=1), (0, 3, 1, 2))

    def by_head(t):   # (b, s, h, d) -> (b, h, d, s)
        return jnp.transpose(t, (0, 2, 3, 1))

    h0 = (jnp.zeros((batch, heads, dk, v.shape[-1]), _F32) if state is None
          else jnp.swapaxes(state.astype(_F32), -1, -2))
    o, h_last, peak = _rule(by_head(q), by_head(k), by_head(v), sc, h0,
                            attention._interpret_default())
    return (_tokens_minor(jnp.transpose(o, (0, 3, 1, 2))[:, :s]),
            jnp.swapaxes(h_last, -1, -2),
            jnp.max(jax.lax.stop_gradient(peak)))



# ------------------------------------- a decay PER KEY CHANNEL (the KDA rule)
#
# Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): the same rule with
# the decay a VECTOR over the key channels, ``alpha_t = exp(g_t)`` in
# ``(key_dim,)``.  With ``H = S^T`` (keys x values):
#
#     H_t = (I - beta_t k_t k_t^T) Diag(alpha_t) H_(t-1) + beta_t k_t v_t^T
#     o_t = H_t^T q_t
#
# The chunked algebra is the scalar rule's with every ``exp(G_t - G_j)``
# moved INSIDE the sum over channels: ``A[t, j] = beta_t sum_c k_tc k_jc
# exp(G_tc - G_jc)`` (j < t), the masked ``q k^T`` likewise, ``exp(G) k``
# and ``exp(G_last - G) k`` channel by channel, and the state decays by rows
# (``Diag(exp(G_last)) H``).  ``sum_c x_tc y_jc exp(G_tc - G_jc)`` cannot be
# factored as ``(x exp(G)) (y exp(-G))^T`` over a chunk — a channel's
# cumulative log-decay passes -88 inside 64 tokens, and ``exp(-G)``
# overflows — so ``decayed_dots`` splits the pairs (t, j) by the LEVEL at
# which they part: at level ``b`` (1, 2, 4, ... chunk / 2) the chunk is cut
# into blocks of ``b`` tokens, and a pair whose ``t`` lies in an odd block
# and ``j`` in the even block before it is computed against that odd block's
# first token ``r``: ``(x_t exp(G_t - G_r)) . (y_j exp(G_r - G_j))``, both
# exponents at most 0 whatever the decay (the paper's sub-chunks, taken all
# the way down: its diagonal blocks are levels 1-8 here).  Every pair j < t
# parts at exactly one level; a level is one product of the chunk's rows.


def kda_reference(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence above, a token at a time in float32: ``delta_reference``
    with ``g (batch, s, heads, key_dim)``, a log-decay a key channel.  With
    ``g`` equal over a head's channels it IS ``delta_reference``."""
    batch, _, heads, dk = q.shape
    if state is None:
        state = jnp.zeros((batch, heads, v.shape[-1], dk), _F32)

    def token(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = s * jnp.exp(g_t)[..., None, :]                 # S Diag(alpha)
        held = jnp.einsum("zhvk,zhk->zhv", s, k_t, precision=_HIGHEST)
        s = s + beta_t[..., None, None] * (
            (v_t - held)[..., :, None] * k_t[..., None, :])
        return s, jnp.einsum("zhvk,zhk->zhv", s, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(
        token, state.astype(_F32),
        tuple(jnp.moveaxis(t.astype(_F32), 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _levels(c: int):
    """The levels of a chunk of ``c`` tokens (any number: a sequence shorter
    than a chunk is one), each ``(the token a row's decay is measured from:
    its own block's first, the token a column's is measured to: the next
    block's first, the pairs (t, j) that part at this level)``, as numpy
    constants."""
    import numpy as np

    tok, out, b = np.arange(c), [], 1
    while b < c:
        blk = tok // b
        out.append((blk * b, np.minimum((blk + 1) * b, c - 1),
                    (blk[:, None] % 2 == 1)
                    & (blk[:, None] - 1 == blk[None, :])))
        b *= 2
    return out


def _level_scales(cum, c):
    """A level at a time: ``(exp(G_t - G_r(t))`` for the rows,
    ``exp(G_r'(j) - G_j)`` for the columns, both ``(..., c, d)`` float32 and
    at most 1, the level's mask ``(c, c))``."""
    for row_ref, col_ref, mask in _levels(c):
        yield (jnp.exp(cum - jnp.take(cum, row_ref, axis=-2)),
               jnp.exp(jnp.take(cum, col_ref, axis=-2) - cum), mask)


def _mm(a, b, spec, dtype):
    """An einsum of operands rounded to ``dtype``, float32 out (float32
    operands at full precision)."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32,
                      precision=_HIGHEST if dtype == _F32 else None)


@jax.custom_vjp
def decayed_dots(x: jax.Array, y: jax.Array, cum: jax.Array) -> jax.Array:
    """``P[t, j] = sum_c x_tc y_jc exp(G_tc - G_jc)`` for ``j < t``, 0
    elsewhere: ``x``, ``y`` ``(..., c, d)`` in the dtype the products take
    their operands in, ``cum (..., c, d)`` float32 the cumulative log-decay
    (never rising along ``c``); float32 out ``(..., c, c)``.  Level by level
    (above): no exponent is ever positive.  The backward pass is written out
    and keeps ``x``, ``y`` and ``cum`` alone: ``dx_t = sum_j dP[t, j] y_j
    exp(G_t - G_j)`` and ``dy_j = sum_t dP[t, j] x_t exp(G_t - G_j)`` by the
    same levels, ``dG = x dx - y dy``."""
    c, dtype = x.shape[-2], x.dtype
    xf, yf = x.astype(_F32), y.astype(_F32)
    out = 0.0
    for rows, cols, mask in _level_scales(cum, c):
        out = out + jnp.where(mask, _mm(xf * rows, yf * cols,
                                        "...td,...jd->...tj", dtype), 0.0)
    return out


def _dots_fwd(x, y, cum):
    return decayed_dots(x, y, cum), (x, y, cum)


def _dots_bwd(res, dp):
    x, y, cum = res
    c, dtype = x.shape[-2], x.dtype
    xf, yf = x.astype(_F32), y.astype(_F32)
    dx = dy = 0.0
    for rows, cols, mask in _level_scales(cum, c):
        d = jnp.where(mask, dp, 0.0)
        dx = dx + rows * _mm(d, yf * cols, "...tj,...jd->...td", dtype)
        dy = dy + cols * _mm(d, xf * rows, "...tj,...td->...jd", dtype)
    return dx.astype(x.dtype), dy.astype(y.dtype), xf * dx - yf * dy


decayed_dots.defvjp(_dots_fwd, _dots_bwd)


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, state: Optional[jax.Array] = None, *,
                chunk: int = 64
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``kda_reference`` in chunks of ``chunk`` tokens:
    ``delta_chunked`` with ``g (batch, s, heads, key_dim)`` float32.
    Returns ``(o`` like ``v``, the state after the last token ``(batch,
    heads, value_dim, key_dim)`` float32, ``state_absmax`` (the largest
    ``|S|`` at any chunk's end) and ``chunk_decay_min`` (the most negative
    cumulative log-decay inside a chunk: under -88 the factored form would
    have overflowed), no gradient through the last two``)``.  Padding as
    ``delta_chunked``'s.

    The Pallas kernels where the shapes are the published ones
    (``kda_kernels_fit``), the XLA form elsewhere: one algorithm, the same
    values to rounding."""
    form = kda_kernels if kda_kernels_fit(
        q.shape[3], v.shape[3], min(chunk, q.shape[1])) else kda_xla
    return form(q, k, v, g, beta, state, chunk=chunk)


def kda_kernels_fit(key_dim: int, value_dim: int, chunk: int) -> bool:
    """Whether a call's shapes tile the chip for ``kda_kernels``: keys and
    values of ONE lane block each (the published 128 / 128), chunks of 64.
    Any number of heads, any batch, any sequence of a chunk or more."""
    return chunk == _CHUNK and key_dim == value_dim == _LANES


def kda_xla(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
            beta: jax.Array, state: Optional[jax.Array] = None, *,
            chunk: int = 64
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``kda_chunked`` as plain XLA: ``delta_xla``'s structure (everything
    but the state for all chunks at once, one ``lax.scan`` across chunks,
    autodiff but for the inverse and the decayed products)."""
    batch, s, heads, dk = q.shape
    dv, dtype = v.shape[-1], q.dtype
    c = min(chunk, s)
    q, k, v, g, beta = pad_to_multiple(c, q, k, v, g, beta)
    n = q.shape[1] // c

    def chunks(t):   # (z, s, h, ...) -> (z, chunk, h, token, ...)
        return jnp.moveaxis(t.reshape(batch, n, c, *t.shape[2:]), 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)              # (z, c, h, t, d)
    beta = chunks(beta.astype(_F32))                       # (z, c, h, t)
    cum = jnp.cumsum(chunks(g.astype(_F32)), axis=-2)      # (z, c, h, t, dk)
    kf, qf = k.astype(_F32), q.astype(_F32)

    a = beta[..., :, None] * decayed_dots(k, k, cum)
    inv = unit_lower_inverse(a).astype(dtype)
    grown = jnp.exp(cum)                                   # exp(G_t)
    u0 = _mm(inv, beta[..., None] * v.astype(_F32), "zchtj,zchjv->zchtv",
             dtype)
    w = _mm(inv, beta[..., None] * grown * kf, "zchtj,zchjk->zchtk",
            dtype).astype(dtype)
    last = cum[..., -1:, :]
    k_end = (kf * jnp.exp(last - cum)).astype(dtype)       # exp(G_last - G_j)
    whole = jnp.exp(last[..., 0, :])                       # (z, c, h, dk)

    def one_chunk(carry, at):
        h, peak = carry                                    # (z, h, k, v) f32
        u0_c, w_c, k_end_c, whole_c = at
        u = (u0_c - _mm(w_c, h, "zhtk,zhkv->zhtv", dtype)).astype(dtype)
        left = whole_c[..., None] * h + _mm(k_end_c, u, "zhtk,zhtv->zhkv",
                                            dtype)
        peak = jnp.maximum(peak, jnp.max(jnp.abs(
            jax.lax.stop_gradient(left))))
        return (left, peak), (h.astype(dtype), u)

    h0 = (jnp.zeros((batch, heads, dk, dv), _F32) if state is None
          else jnp.swapaxes(state.astype(_F32), -1, -2))
    (h_last, peak), (entering, u) = jax.lax.scan(
        one_chunk, (h0, jnp.zeros((), _F32)),
        tuple(jnp.moveaxis(t, 1, 0) for t in (u0, w, k_end, whole)))
    entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)

    # the masked q k^T: the pairs j < t by their levels, j = t undecayed
    m = decayed_dots(q, k, cum) + jnp.sum(qf * kf, -1)[..., None] * jnp.eye(
        c, dtype=_F32)
    o = _mm(m, u, "zchtj,zchjv->zchtv", dtype)
    o = o + _mm(qf * grown, entering, "zchtk,zchkv->zchtv", dtype)
    o = jnp.moveaxis(o, 2, 3).reshape(batch, n * c, heads, dv)[:, :s]
    return (o.astype(v.dtype), jnp.swapaxes(h_last, -1, -2), peak,
            jnp.min(jax.lax.stop_gradient(cum)))


# ------------------------------------- the Pallas form of the KDA rule
#
# (its one import of its own stands HERE, with the code behind the scalar
# rule's, so that ``delta_fwd`` / ``delta_bwd``, whose lowered text embeds
# their line numbers, keep their compile-cache keys)
from jax.ad_checkpoint import checkpoint_name  # noqa: E402
#
# ``kdarule_fwd`` / ``kdarule_bwd``: the scalar rule's kernels (above) with
# the decay a ``(tokens, 128)`` tile.  What changes: a token's raw
# log-decays come in as a tile like k's (float32), and EVERY sum of them the
# pair needs — the cumulative log-decay of a chunk, and at each of the six
# levels the decay from a row's block start and to a column's next block
# start — comes out of ONE doubling scan of that tile along the tokens (the
# sublanes) on the vector unit, ``_kda_decay_sums``: ``decayed_dots``'
# levels ARE the stages of a doubling prefix sum, so a level's sums are the
# level below's plus, in every other block, one row of the neighbouring
# block.  Every exponent stays a sum of numbers that are never positive,
# made by a tree of float32 additions (exact to a few 2^-24 OF ITSELF); no
# difference of two large cumulative sums is ever taken.  Until PR 63 each
# of the twelve sums was a 0/1 matrix of the pair's tokens times the tile's
# three bfloat16 parts: 36 of a pair's about 115 MXU passes.  Stacking the
# selections into one product of 1408 rows kept every pass and read 10 %
# SLOWER (PR 59); the scan takes them out: ``kda.kernel_ms`` 152.98 ->
# 123.96 and ``train_tokens_per_s`` 14133 -> 14877 in Kimi-Linear's cell
# (ledger, PR 60: this change, refused on another cell's ``setup_s``).  The
# gradient to the raw log-decays is the lower-triangular 0/1 matrix,
# transposed, times the gradient to the cumulative sums, which for the
# decayed products is ``x dx - y dy`` (``decayed_dots``): the ONE product of
# a three-part split left (``_split3``, ``_sum01``: a 0/1 matrix is exact in
# bfloat16, three MXU passes, float32 to the last bit).
#
# WHAT THE PAIR HANDS FROM FORWARD TO BACKWARD (PR 65): the scalar rule's
# ``delta_fwd`` writes the float32 state entering EVERY chunk, which at 128
# x 128 is 268 MB a layer of 32 heads x 8192 tokens — too dear for a layer
# checkpoint to hold, so until PR 65 the checkpoint's backward pass ran the
# whole of ``kdarule_fwd`` again (decayed products, inverse and all) to get
# ``states``, ``tb`` and ``o`` back: 44.6 ms of Kimi-Linear's 551 ms step.
# ``kdarule_fwd`` now writes the state entering each GRID STEP (``_plan``:
# up to ``_STEP_PAIRS`` pairs = 8 chunks; 33.5 MB a layer), the pairs'
# inverses ``tb`` (bf16, 67 MB) and ``o`` (67 MB), ``_kda_rule_fwd`` names
# the three (``KDA_SAVED_RESIDUALS``) and the checkpoint keeps them: no
# second ``kdarule_fwd``.  ``kdarule_bwd`` takes a grid step from the end
# and first walks the step's pairs FORWARD from the state that entered it
# (``_kda_sweep``): with ``tb`` at hand a chunk's state costs ``u0 = T vb``,
# ``w = T kb``, ``u = u0 - w H`` and ``H' = exp(G_last) H + k_end^T u`` — the
# first three the backward made anyway — and none of the decayed products or
# the inverse's ten float32 products (``_pair_inverse``, sixty MXU passes).
# Forward kernel and sweep advance the state through ONE function,
# ``_kda_chunk`` (the same operands, casts and order), so the rebuilt states
# are the forward's to the bit
# (``tests/test_kda.py``) and the gradient is what it was.  The sweep's
# states and each pair's ``u0``, ``w``, ``u`` stay in VMEM scratch for the
# reverse walk (512 + 384 KB at four pairs a step).


def _split3(x):
    """float32 ``x (n, 128)`` as three bfloat16 parts side by side ``(n,
    384)``: their sum is ``x`` to 2^-24."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(_F32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(_F32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=1)


def _sum01(sel, parts, contract=(1, 0)):
    """The 0/1 matrix ``sel`` (bool) times the float32 tile whose
    ``_split3`` is ``parts``: exact sums of the tile's rows."""
    n = parts.shape[1] // 3
    out = _dot(sel.astype(jnp.bfloat16), parts, contract)
    return out[:, :n] + out[:, n:2 * n] + out[:, 2 * n:]


_KDA_LEVELS = 6      # blocks of 1, 2, ... 32 tokens inside a chunk of 64


def _kda_selectors(row, col):
    """For a pair's tokens ``t`` (rows) and ``i`` (columns): ``same`` (i in
    t's chunk), ``lower`` (and at or before t) and, a level, the pairs
    ``(t, j = i)`` that part there: t in an odd block, j in the even block
    before it."""
    same = (row >> 6) == (col >> 6)
    return same, same & (col <= row), [
        (((row >> level) & 1) == 1) & ((row >> level) - 1 == (col >> level))
        for level in range(_KDA_LEVELS)]


def _tile_roll(x, shift):
    """``x (128, 128)`` float32 with the rows of every ``(8, 128)`` tile
    rolled by ``shift``: row ``t`` takes row ``t - shift`` OF ITS OWN TILE."""
    return pltpu.roll(x.reshape(_PAIR // 8, 8, _LANES), shift % 8, 1
                      ).reshape(_PAIR, _LANES)


def _kda_decay_sums(g, row):
    """Every sum of the raw log-decays ``g (128 tokens, 128 channels)``
    float32 a pair needs, by one doubling scan along the tokens; ``row
    (128, 128)`` is the tokens' index.  A block of ``2b = 2^(l+1)`` tokens
    is two HALVES, blocks of ``b``; ``start_l(t)`` is the first token of
    ``t``'s block of ``b``.  Returns ``(rows, cols, cum)``:

    ``rows[l][t]``, the sum over ``start_l(t) < i <= t`` (``None`` at level
    0, where it is empty): ``R_(l+1) = R_l`` plus, in a second half, that
    half's first log-decay and the whole first half (``R_l`` at the first
    half's last row);
    ``cols[l][t]``, the sum over ``t < i <= start_l(t) + b`` — FOR ``t`` IN
    A FIRST HALF, the columns the level's mask keeps; in a second half some
    other sum of log-decays.  With ``D_l`` the sum after ``t`` to the end of
    its block of ``b``: ``C_l = D_l`` plus the second half's first
    log-decay, and ``D_(l+1) = C_l + D_l`` at that row;
    ``cum[t]``, the chunk's cumulative sum: ``R_6`` plus the chunk's first
    ``g``.

    Halves of 8 tokens and more are whole ``(8, 128)`` tiles: a stage is one
    row's sublane broadcast and an add on half the tiles.  Under that a row
    is fetched by rolls inside the tiles and a select on a bit of the row
    index; what a roll wraps round a tile lands only where no level's mask
    keeps it, and is a sum of log-decays like any other.  So every value is
    a sum of numbers that are never positive, by float32 additions no deeper
    than twelve: no ``exp`` of any entry passes 1."""
    in_tile = 3                                # blocks of 1, 2, 4 tokens
    odd = [((row >> level) & 1) == 1 for level in range(in_tile)]

    def from_first(x, level):   # a block's first row, down the block
        for i in range(level):
            x = jnp.where(odd[i], _tile_roll(x, 1 << i), x)
        return x

    def from_last(x, level):    # a block's last row, up the block
        for i in range(level):
            x = jnp.where(odd[i], x, _tile_roll(x, -(1 << i)))
        return x

    after = _tile_roll(g, -1)                  # the log-decay after t's
    rows, cols = [None], [after]
    r, d = jnp.where(odd[0], g, 0.0), jnp.where(odd[0], 0.0, after)
    for level in range(1, in_tile):
        # ... after t's block: the second half's first, seen from the first
        after = jnp.where(odd[level - 1], after,
                          _tile_roll(after, -(1 << (level - 1))))
        rows.append(r)
        cols.append(d + after)
        r = r + jnp.where(odd[level],
                          from_first(g + _tile_roll(r, 1), level), 0.0)
        d = d + jnp.where(odd[level], 0.0,
                          from_last(_tile_roll(g + d, -1), level))

    def halves(x, level):       # (blocks of 2b, 2b, 128)
        return x.reshape(_PAIR >> (level + 1), 2 << level, _LANES)

    def whole(first, second):
        return jnp.concatenate([first, second], axis=1).reshape(_PAIR, _LANES)

    for level in range(in_tile, _KDA_LEVELS):
        b = 1 << level
        r2, d2, g2 = halves(r, level), halves(d, level), halves(g, level)
        c = d2[:, :b] + g2[:, b:b + 1]
        rows.append(r)
        cols.append(whole(c, d2[:, b:]))
        r = whole(r2[:, :b], r2[:, b:] + (g2[:, b:b + 1] + r2[:, b - 1:b]))
        d = whole(c + d2[:, b:b + 1], d2[:, b:])   # (the last is not read)
    g2 = halves(g, _KDA_LEVELS - 1)
    return rows, cols, r + jnp.broadcast_to(g2[:, :1], g2.shape).reshape(
        _PAIR, _LANES)


def _kda_state_terms(k, v, g, beta_r):
    """The part of a pair's terms that the STATE's walk through its two
    chunks takes — ``k``, ``v (128, 128)``, raw log-decays ``g (128, 128)``
    float32, the row of betas ``(1, 128)`` —: the decay sums, ``exp(G)``,
    ``exp(G_last - G)``, ``beta v``, ``beta exp(G) k`` and ``exp(G_last -
    G) k``.  No decayed product, no inverse, nothing of ``q``: what the
    backward kernel's sweep stops at."""
    dtype = k.dtype
    big = _HIGHEST if dtype == _F32 else None
    row, col = _iota((_PAIR, _PAIR), 0), _iota((_PAIR, _PAIR), 1)
    eye = row == col
    rows, cols, cum = _kda_decay_sums(g, row)               # cum: G_t
    beta = jnp.sum(jnp.where(eye, beta_r, 0.0), axis=1, keepdims=True)
    tok = _iota((_PAIR, 1), 0)
    lasts = [cum[_CHUNK - 1:_CHUNK, :], cum[_PAIR - 1:_PAIR, :]]  # (1, 128)
    grown = jnp.exp(cum)
    to_end = jnp.exp(jnp.where(tok < _CHUNK, lasts[0], lasts[1]) - cum)
    kf = k.astype(_F32)
    return dict(
        big=big, eye=eye, row=row, col=col, tok=tok, rows=rows, cols=cols,
        cum=cum, beta=beta, grown=grown, to_end=to_end, lasts=lasts, kf=kf,
        vb=(beta * v.astype(_F32)).astype(dtype),
        kb=(beta * grown * kf).astype(dtype),
        k_end=(to_end * kf).astype(dtype))


def _kda_pair_terms(q, k, v, g, beta_r):
    """What forward and backward both make of one pair from its ``q``,
    ``k``, ``v (128, 128)``, raw log-decays ``g (128, 128)`` float32 and
    the row of betas ``(1, 128)``, before anything reads the state:
    ``_kda_state_terms`` and, on top, the decayed products level by level
    (``kk``, ``qk``) and ``exp(G) q``."""
    t = _kda_state_terms(k, v, g, beta_r)
    dtype, big, row, col, eye = q.dtype, t["big"], t["row"], t["col"], t["eye"]
    same, lower, masks = _kda_selectors(row, col)
    kf, qf = t["kf"], q.astype(_F32)

    def scaled():
        """A level at a time: the rows' and the columns' decays, the
        decayed operands as the products take them, the level's pairs."""
        for r, c, mask in zip(t["rows"], t["cols"], masks):
            er, ec = None if r is None else jnp.exp(r), jnp.exp(c)
            x = kf if er is None else kf * er
            qx = qf if er is None else qf * er
            yield (er, ec, x.astype(dtype), qx.astype(dtype),
                   (kf * ec).astype(dtype), mask)

    kk = qk = jnp.zeros((_PAIR, _PAIR), _F32)
    for _, _, x, qx, y, mask in scaled():
        both = _dot(jnp.concatenate([x, qx], axis=0), y, (1, 1), big)
        kk = kk + jnp.where(mask, both[:_PAIR], 0.0)
        qk = qk + jnp.where(mask, both[_PAIR:], 0.0)
    on_diag = jnp.sum(qf * kf, axis=1, keepdims=True)       # j = t: no decay
    return dict(
        t, below=same & (col < row), upto=same & (col <= row), lower=lower,
        scaled=scaled, qf=qf, kk=kk, qk=qk + jnp.where(eye, on_diag, 0.0),
        a=t["beta"] * kk, qg=(t["grown"] * qf).astype(dtype))


def _column(eye, r):   # (1, 128) -> (128, 1)
    return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)


def _row(eye, c):      # (128, 1) -> (1, 128)
    return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)


def _kda_chunk(t, i, h, u0, w):
    """Chunk ``i`` of a pair from the state ``h (keys, values)`` float32
    that enters it: ``(h`` as the products read it, the chunk's new values
    ``u``, the state it leaves``)``.  The forward kernel and the backward
    kernel's sweep both walk the state through THIS, so the states the
    sweep rebuilds are the forward's to the bit."""
    rows, big = _HALVES[i], t["big"]
    hb = h.astype(w.dtype)
    u = (u0[rows] - _dot(w[rows], hb, (1, 0), big)).astype(w.dtype)
    return hb, u, _column(t["eye"], jnp.exp(t["lasts"][i])) * h + _dot(
        t["k_end"][rows], u, (0, 0), big)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref,
                    o_ref, entering_ref, tb_ref, hlast_ref, peak_ref, low_ref,
                    h_scr, *, pairs):
    """``_fwd_kernel`` with a decay a key channel: the state ``(keys,
    values)`` decays by ROWS.  Beside ``o`` it writes what the backward
    kernel cannot cheaply remake, and no more: each pair's inverse as the
    products read it and the state entering the GRID STEP (not every
    chunk: ``kdarule_bwd`` walks the step's chunks forward again from it).
    Beside the state's largest entry it reports the largest ``-G`` inside
    a chunk."""
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_step():
        h_scr[...] = h0_ref[0, 0]
        peak_ref[...] = jnp.zeros_like(peak_ref)
        low_ref[...] = jnp.zeros_like(low_ref)

    entering_ref[0, 0, 0] = h_scr[...]

    def pair(p, carry):
        peak, low = carry
        at = pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)
        t = _kda_pair_terms(
            q_ref[0, 0, :, at].T, k_ref[0, 0, :, at].T, v_ref[0, 0, :, at].T,
            g_ref[0, 0, :, at].T, beta_ref[0, 0, :, at])
        big = t["big"]
        tb = _pair_inverse(t["a"], t["row"], t["col"]).astype(dtype)
        tb_ref[0, 0, at, :] = tb
        u0 = _dot(tb, t["vb"], (1, 0), big)
        w = _dot(tb, t["kb"], (1, 0), big).astype(dtype)
        h, us, from_state = h_scr[...], [], []
        for i, rows in enumerate(_HALVES):
            hb, u, h = _kda_chunk(t, i, h, u0, w)
            from_state.append(_dot(t["qg"][rows], hb, (1, 0), big))
            peak = jnp.maximum(peak, jnp.max(jnp.abs(h)))
            us.append(u)
        h_scr[...] = h
        o = _dot(t["qk"].astype(dtype), _halves(us), (1, 0), big) + _halves(
            from_state)
        o_ref[0, 0, :, at] = o.astype(o_ref.dtype).T
        return peak, jnp.maximum(low, jnp.max(-t["cum"]))

    zero = jnp.zeros((), _F32)
    peak, low = _each_pair(pairs, pair, (zero, zero))
    peak_ref[...] = jnp.maximum(peak_ref[...], peak)
    low_ref[...] = jnp.maximum(low_ref[...], low)
    hlast_ref[0, 0] = h_scr[...]


def _kda_sweep(k_ref, v_ref, g_ref, beta_ref, entering_ref, tb_ref, hs_scr,
               u0_scr, w_scr, u_scr, pairs):
    """A grid step's pairs FORWARD from the state that entered the step,
    for the backward kernel: the state entering each chunk into ``hs_scr
    (2 pairs, keys, values)`` float32, each pair's ``u0 = T vb``, ``w = T
    kb`` and ``u = u0 - w H`` into ``u0_scr``, ``w_scr``, ``u_scr (pairs,
    128, 128)`` as the products read them.  ``_kda_state_terms`` and
    ``_kda_chunk`` alone: four products a pair."""
    dtype = k_ref.dtype

    def pair(p, h):
        at = pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)
        t = _kda_state_terms(k_ref[0, 0, :, at].T, v_ref[0, 0, :, at].T,
                             g_ref[0, 0, :, at].T, beta_ref[0, 0, :, at])
        tb = tb_ref[0, 0, at, :]
        u0 = _dot(tb, t["vb"], (1, 0), t["big"])
        w = _dot(tb, t["kb"], (1, 0), t["big"]).astype(dtype)
        us = []
        for i in range(2):
            hs_scr[2 * p + i] = h
            _, u, h = _kda_chunk(t, i, h, u0, w)
            us.append(u)
        u0_scr[p], w_scr[p], u_scr[p] = u0.astype(dtype), w, _halves(us)
        return h

    _each_pair(pairs, pair, entering_ref[0, 0, 0])


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, entering_ref,
                    tb_ref, do_ref, dhl_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                    dbeta_ref, dh0_ref, dh_scr, hs_scr, u0_scr, w_scr, u_scr,
                    *, pairs):
    """``_bwd_kernel`` with a decay a key channel, and WITHOUT the forward's
    per-chunk states: a grid step (taken from the end) first SWEEPS its
    pairs forward from the state that entered the step — the decay sums,
    ``u0 = T vb``, ``w = T kb`` and, a chunk at a time, ``u = u0 - w H``,
    ``H' = exp(G_last) H + k_end^T u`` (``_kda_chunk``: the forward
    kernel's sums in the forward kernel's order, none of its decayed
    products, no inverse) — into VMEM: the state entering each chunk
    (``hs_scr``, float32) and each pair's ``u0``, ``w``, ``u`` as the
    products read them; then it walks the pairs in reverse and makes none
    of them again.  The gradient to a token's cumulative log-decays is a
    ``(tokens, 128)`` tile: of the decayed products ``x dx - y dy`` (the
    gradients of their operands, the levels summed), of ``exp(G)`` and
    ``exp(G_last - G)`` what their products send back, channel by channel;
    a chunk's last token collects what ``G_last`` gets.  The raw
    log-decays' gradient is the 0/1 lower-triangular matrix, transposed,
    times that tile."""
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_step():
        dh_scr[...] = dhl_ref[0, 0]

    _kda_sweep(k_ref, v_ref, g_ref, beta_ref, entering_ref, tb_ref, hs_scr,
               u0_scr, w_scr, u_scr, pairs)

    def pair(j, carry):
        p = pairs - 1 - j
        at = pl.ds(pl.multiple_of(p * _PAIR, _PAIR), _PAIR)
        q, k, v = (ref[0, 0, :, at].T for ref in (q_ref, k_ref, v_ref))
        t = _kda_pair_terms(q, k, v, g_ref[0, 0, :, at].T,
                            beta_ref[0, 0, :, at])
        big, tok, beta, grown = t["big"], t["tok"], t["beta"], t["grown"]
        eye, kf, qf = t["eye"], t["kf"], t["qf"]
        tb, do = tb_ref[0, 0, at, :], do_ref[0, 0, :, at].T
        hs = [hs_scr[2 * p + i] for i in range(2)]
        hbs = [h.astype(dtype) for h in hs]
        u0, w, u = u0_scr[p], w_scr[p], u_scr[p]
        # o = (masked decayed q k^T) u + (exp(G) q) H
        dm = jnp.where(t["upto"], _dot(do, u, (1, 1), big), 0.0)
        du_o = _dot(t["qk"].astype(dtype), do, (0, 0), big)
        dqg = _halves([_dot(do[r], hb, (1, 1), big)
                       for r, hb in zip(_HALVES, hbs)])

        dh, walked = dh_scr[...], {}
        for i in (1, 0):   # du, dw, d k_end, and what <dH', H> gives G_last
            r, hb = _HALVES[i], hbs[i]
            whole = _column(eye, jnp.exp(t["lasts"][i]))        # (keys, 1)
            dhb = dh.astype(dtype)
            du = du_o[r] + _dot(t["k_end"][r], dhb, (1, 0), big)
            dub = du.astype(dtype)
            walked[i] = (du, -_dot(dub, hb, (1, 1), big),
                         _dot(u[r], dhb, (1, 1), big),
                         _row(eye, whole * jnp.sum(dh * hs[i], axis=1,
                                                   keepdims=True)))
            dh = (whole * dh + _dot(t["qg"][r], do[r], (0, 0), big)
                  - _dot(w[r], dub, (0, 0), big))
        dh_scr[...] = dh
        du, dw, dk_end = (_halves([walked[i][n] for i in range(2)])
                          for n in range(3))

        dvb = _dot(tb, du.astype(dtype), (0, 0), big)      # T^T du0
        dkb = _dot(tb, dw.astype(dtype), (0, 0), big)      # T^T dw
        da = jnp.where(t["below"], -(
            _dot(dvb.astype(dtype), u0, (1, 1), big)
            + _dot(dkb.astype(dtype), w, (1, 1), big)), 0.0)
        dkk = beta * da
        # the decayed products, level by level: what their operands get
        dk_rows = dq_rows = dk_cols = jnp.zeros((_PAIR, _PAIR), _F32)
        for er, ec, x, qx, y, mask in t["scaled"]():
            d = jnp.concatenate([jnp.where(mask, dkk, 0.0),
                                 jnp.where(mask, dm, 0.0)], axis=0
                                ).astype(dtype)                 # (256, 128)
            dxs = _dot(d, y, (1, 0), big)
            dy = _dot(d, jnp.concatenate([x, qx], axis=0), (0, 0), big)
            dx, dqx = dxs[:_PAIR], dxs[_PAIR:]
            dk_rows = dk_rows + (dx if er is None else er * dx)
            dq_rows = dq_rows + (dqx if er is None else er * dqx)
            dk_cols = dk_cols + ec * dy
        on_diag = jnp.sum(jnp.where(eye, dm, 0.0), axis=1, keepdims=True)
        by_kb = beta * grown * dkb                          # d(k) through kb
        to_ends = t["to_end"] * dk_end                      # ... through k_end
        dcum = (kf * (dk_rows - dk_cols) + qf * dq_rows
                + kf * by_kb + grown * dqg * qf - kf * to_ends)
        for i in range(2):   # what a chunk's last token collects
            mine = (tok >= i * _CHUNK) & (tok < (i + 1) * _CHUNK)
            dcum = dcum + jnp.where(
                tok == i * _CHUNK + _CHUNK - 1,
                jnp.sum(jnp.where(mine, kf * to_ends, 0.0), axis=0,
                        keepdims=True) + walked[i][3], 0.0)
        dbeta = (jnp.sum(da * t["kk"], axis=1, keepdims=True)
                 + jnp.sum(dkb * grown * kf, axis=1, keepdims=True)
                 + jnp.sum(dvb * v.astype(_F32), axis=1, keepdims=True))
        dg_ref[0, 0, :, at] = _sum01(t["lower"], _split3(dcum), (0, 0)).T
        dbeta_ref[0, 0, :, at] = _row(eye, dbeta)
        dq_ref[0, 0, :, at] = (dq_rows + on_diag * kf
                               + grown * dqg).astype(dq_ref.dtype).T
        dk_ref[0, 0, :, at] = (dk_rows + dk_cols + on_diag * qf + by_kb
                               + to_ends).astype(dk_ref.dtype).T
        dv_ref[0, 0, :, at] = (beta * dvb).astype(dv_ref.dtype).T
        return carry

    _each_pair(pairs, pair, 0)
    dh0_ref[0, 0] = dh_scr[...]


def _kda_plan(q, reverse):
    """``_plan`` for the KDA pair, with the row of betas and ``entering``,
    ONE state a grid step."""
    sp = _plan(q, q, reverse)
    steps, tokens, keys = sp["grid"][2], sp["pairs"] * _PAIR, q.shape[2]

    def step(c_):
        return steps - 1 - c_ if reverse else c_

    sp["beta"] = pl.BlockSpec(
        (1, 1, 1, tokens), lambda b_, h_, c_: (b_, h_, 0, step(c_)))
    sp["entering"] = pl.BlockSpec(
        (1, 1, 1, keys, keys), lambda b_, h_, c_: (b_, h_, step(c_), 0, 0))
    return sp


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_fwd_call(q, k, v, g, beta, h0, *, interpret):
    """``q``, ``k``, ``v`` ``(b, heads, 128, s)``, ``g`` likewise float32
    (a token's RAW log-decay a key channel), ``beta (b, heads, 1, s)``
    float32, ``h0 (b, heads, 128, 128)`` float32; ``s`` a multiple of 128.
    Returns ``o`` like ``v``, the state entering every GRID STEP ``(b,
    heads, steps, 128, 128)`` float32 (``_plan``: a step is up to
    ``_STEP_PAIRS`` pairs of chunks), every pair's inverse ``(b, heads, s,
    128)`` in ``q.dtype``, the last state like ``h0``, its largest entry
    at any chunk's end and the largest ``-G`` inside a chunk, both ``(b,
    heads, 8, 128)``."""
    sp = _kda_plan(q, reverse=False)
    batch, heads, keys, s = q.shape
    stat = jax.ShapeDtypeStruct((batch, heads, 8, _LANES), _F32)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, pairs=sp["pairs"]),
        grid=sp["grid"],
        in_specs=[sp["qk"], sp["qk"], sp["qk"], sp["qk"], sp["beta"],
                  sp["state"]],
        out_specs=[sp["qk"], sp["entering"], sp["tb"], sp["state"],
                   sp["peak"], sp["peak"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, heads, sp["grid"][2], keys, keys), _F32),
                   jax.ShapeDtypeStruct((batch, heads, s, _PAIR), q.dtype),
                   jax.ShapeDtypeStruct(h0.shape, _F32), stat, stat],
        scratch_shapes=[sp["carry"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="kdarule_fwd",
    )(q, k, v, g, beta, h0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_bwd_call(q, k, v, g, beta, entering, tb, do, dh_last, *, interpret):
    """Gradients to ``q``, ``k``, ``v``, ``g``, ``beta`` and ``h0``, each
    like its argument, from the forward's ``entering`` and ``tb``.  VMEM
    beside the state's gradient: a step's chunk states (512 KB at four
    pairs) and its pairs' ``u0``, ``w``, ``u`` (96 KB a pair)."""
    sp = _kda_plan(q, reverse=True)
    pairs, keys = sp["pairs"], q.shape[2]
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    of_pair = pltpu.VMEM((pairs, _PAIR, keys), q.dtype)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, pairs=pairs),
        grid=sp["grid"],
        in_specs=[sp["qk"], sp["qk"], sp["qk"], sp["qk"], sp["beta"],
                  sp["entering"], sp["tb"], sp["qk"], sp["state"]],
        out_specs=[sp["qk"], sp["qk"], sp["qk"], sp["qk"], sp["beta"],
                   sp["state"]],
        out_shape=[like(q), like(k), like(v), like(g), like(beta),
                   like(dh_last)],
        scratch_shapes=[sp["carry"],
                        pltpu.VMEM((2 * pairs, keys, keys), _F32),
                        of_pair, of_pair, of_pair],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="kdarule_bwd",
    )(q, k, v, g, beta, entering, tb, do, dh_last)


# What a layer checkpoint keeps of the rule (``models/blocks/kda.py`` hands
# these names to ``models/llama.py``'s policy), named where they are made as
# ``ops/attention.py`` names ``flash_out`` and ``flash_lse``: with the
# kernel's output (``kda_out`` reads it), each pair's inverse and the state
# entering each grid step held, the rematerialised pass needs no second
# ``kdarule_fwd``.  At 8192 tokens of 32 heads (4096 of 64): 67 + 67 + 33.5
# MB a layer, where the float32 state entering every CHUNK was 268 MB.
KDA_SAVED_RESIDUALS = ("kda_rule_out", "kda_rule_inverse",
                       "kda_rule_entering")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda_rule(q, k, v, g, beta, h0, interpret):
    o, _, _, h_last, peak, low = _kda_fwd_call(q, k, v, g, beta, h0,
                                               interpret=interpret)
    return o, h_last, peak, low


def _kda_rule_fwd(q, k, v, g, beta, h0, interpret):
    o, entering, tb, h_last, peak, low = _kda_fwd_call(
        q, k, v, g, beta, h0, interpret=interpret)
    o, tb, entering = (checkpoint_name(t, name) for t, name in zip(
        (o, tb, entering), KDA_SAVED_RESIDUALS))
    return (o, h_last, peak, low), (q, k, v, g, beta, entering, tb)


def _kda_rule_bwd(interpret, res, cts):
    do, dh_last, _, _ = cts
    return _kda_bwd_call(*res, do, dh_last, interpret=interpret)


_kda_rule.defvjp(_kda_rule_fwd, _kda_rule_bwd)


def kda_kernels(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, state: Optional[jax.Array] = None, *,
                chunk: int = 64
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``kda_chunked`` through the Pallas kernels (keys and values of 128,
    chunks of 64; compiled on the TPU, interpreted elsewhere).  XLA pads
    the sequence to whole pairs of chunks (tokens that neither decay nor
    write) and hands q, k, v and the RAW log-decays over a head at a time
    with the sequence as the minor dimension, as ``delta_kernels`` does; the
    cumulative sums are the kernels' own."""
    batch, s, heads, dk = q.shape
    if not kda_kernels_fit(dk, v.shape[-1], min(chunk, s)):
        raise ValueError("kda_kernels takes keys and values of "
                         f"{_LANES} at chunks of {_CHUNK}")
    q, k, v, g, beta = pad_to_multiple(
        _PAIR, *(_tokens_minor(t) for t in (
            q, k, v, g.astype(_F32), beta.astype(_F32))))

    def by_head(t):   # (b, s, h, d) -> (b, h, d, s)
        return jnp.transpose(t, (0, 2, 3, 1))

    h0 = (jnp.zeros((batch, heads, dk, dk), _F32) if state is None
          else jnp.swapaxes(state.astype(_F32), -1, -2))
    o, h_last, peak, low = _kda_rule(
        by_head(q), by_head(k), by_head(v), by_head(g),
        jnp.transpose(beta, (0, 2, 1))[:, :, None, :], h0,
        attention._interpret_default())
    return (_tokens_minor(jnp.transpose(o, (0, 3, 1, 2))[:, :s]),
            jnp.swapaxes(h_last, -1, -2),
            jnp.max(jax.lax.stop_gradient(peak)),
            -jnp.max(jax.lax.stop_gradient(low)))
