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

Everything but ``H`` is computed for all chunks at once; ONE state a head
is carried across chunks by ``lax.scan`` (not unrolled), which makes ``U``
and hands out the state entering each chunk; the outputs follow for all
chunks at once.  ``T`` is the inverse of a unit lower triangular matrix of
``chunk`` rows (``unit_lower_inverse``): the inverses of its diagonal
blocks of two rows, merged by doubling, ten products of ``chunk`` rows at
64 in place of a substitution of ``chunk`` dependent steps; its backward
pass is written out (``dA = -T^T dT T^T``), the rest is autodiff.  Plain XLA: the per-chunk matrices and
the entering states are arrays in memory.

Precision: log-decays, their cumulative sums, every ``exp``, ``A``, the
inverse and the carried state are float32 (the inverse's products at
``Precision.HIGHEST``); the operands of the big products (``k k^T``, ``q
k^T``, ``T`` times values and keys, everything times the state) are in
``q.dtype`` with float32 accumulation.  ``state_absmax`` — the largest
``|S|`` at a chunk's end — is the first number to read when a comparison
with the recurrence drifts.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.ssm import exp_where, pad_to_multiple

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


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
    as it is."""
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
