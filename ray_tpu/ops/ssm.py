"""State-space mixer ops: what a Mamba-2 layer computes between its two
projections (Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060).

Per head, with a scalar decay a token, the layer is the recurrence

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        (head_dim x state)
    y_t = H_t C_t + D x_t

``ssd_chunked`` computes it in chunks (the paper's SSD form): inside a
chunk the outputs are ``(C B^T o L) (dt x)`` with ``L[i, j] = exp(sum of
dt A over j < k <= i)`` for ``j <= i`` and 0 above the diagonal — a masked
matmul, which is what the MXU is for — and ONE state a chunk is carried
across chunks.  ``ssd_reference`` is the recurrence itself, a token at a
time in float32: what the tests hold the chunked form to, as
``ops/attention.py`` has ``mha_reference`` beside its kernels.

Plain XLA, differentiated by autodiff: under the layer checkpoint
(``models/llama.py::_checkpoint``) a layer's backward pass runs its
forward again, so the intra-chunk matrices of one layer at a time exist
(``(chunks, heads, chunk, chunk)``: 268 MB in bfloat16 at 8192 tokens, 64
heads, chunks of 256).  A Pallas kernel that keeps them in VMEM is sized by
the benchmark's ``ssm.scan_roofline`` (ROADMAP S10).

Precision: the decays (``dt A``, their cumulative sums, every ``exp``) and
the state carried across chunks are float32; the operands of the big
products (``C B^T``, the masked matrix times ``dt x``, ``B^T`` times the
decayed ``dt x``, ``C`` times the entering state) are in ``x.dtype`` with
float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import rms_norm

_F32 = jnp.float32


def _conv_pre(x, weight, bias):
    """The convolution before its SiLU, float32: ``bias + sum_i weight[i]
    * x[t - (k-1) + i]``.  The shifted copies are cut from ``x`` in its
    own dtype and widened inside the sum, so no float32 copy of ``x`` is
    written."""
    k, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    return bias.astype(_F32) + sum(
        padded[:, i:i + s].astype(_F32) * w[i] for i in range(k))


@jax.custom_vjp
def causal_conv1d(x: jax.Array, weight: jax.Array, bias: jax.Array
                  ) -> jax.Array:
    """SiLU of the causal depthwise convolution of ``x (b, s, c)`` along
    ``s``: ``y[t] = bias + sum_i weight[i] * x[t - (k-1) + i]`` with
    ``weight (k, c)`` and zeros before the sequence — ``weight[k-1]``
    meets the current token, as a torch ``Conv1d(groups=c, padding=k-1)``
    cut to ``s`` outputs has it.  Float32 inside, ``x.dtype`` out.

    Its backward pass is written out (autodiff of pad-and-slice writes one
    float32 ``(b, s, c)`` array a tap and sums them in a second pass): the
    gradient to ``x`` is the same convolution run against time."""
    return jax.nn.silu(_conv_pre(x, weight, bias)).astype(x.dtype)


def _conv_fwd(x, weight, bias):
    return causal_conv1d(x, weight, bias), (x, weight, bias)


def _conv_bwd(res, dy):
    x, weight, bias = res
    k, s = weight.shape[0], x.shape[1]
    pre = _conv_pre(x, weight, bias)          # cheap to run again
    sig = jax.nn.sigmoid(pre)
    dpre = dy.astype(_F32) * sig * (1.0 + pre * (1.0 - sig))
    w = weight.astype(_F32)
    ahead = jnp.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(ahead[:, k - 1 - i:k - 1 - i + s] * w[i] for i in range(k))
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(dpre * padded[:, i:i + s].astype(_F32), (0, 1))
                    for i in range(k)])
    return (dx.astype(x.dtype), dw.astype(weight.dtype),
            jnp.sum(dpre, (0, 1)).astype(bias.dtype))


causal_conv1d.defvjp(_conv_fwd, _conv_bwd)


def gated_rms_norm(y: jax.Array, z: jax.Array, weight: jax.Array,
                   eps: float) -> jax.Array:
    """Mamba-2's output norm: the GATE FIRST (``y * silu(z)``), then one
    RMSNorm over the whole last dimension; float32 inside, ``y.dtype``
    out.  (The other order, norm then gate, is Mamba-2's
    ``norm_before_gate``, which the published models do not use.)"""
    gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    return rms_norm(gated, weight, eps).astype(y.dtype)


def _exp_where(mask, x):
    """``exp(x)`` where ``mask`` and 0 elsewhere, with a gradient that is
    finite there too (the masked side may overflow)."""
    return jnp.exp(jnp.where(mask, x, -jnp.inf))


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, *, chunk: int) -> jax.Array:
    """The recurrence above for every head, in chunks of ``chunk`` tokens.

    ``x (batch, s, heads, head_dim)``; ``dt (batch, s, heads)`` float32,
    positive (after its softplus); ``a (heads,)`` float32, negative;
    ``b``, ``c`` ``(batch, s, groups, state)``, a group shared by
    ``heads / groups`` heads; ``d (heads,)``.  Returns ``y`` like ``x``.
    A sequence that is no multiple of the chunk is padded with tokens
    whose ``dt`` is 0 (no decay, no input), which the causal order keeps
    from every real output."""
    batch, s, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // q
    dtype = x.dtype
    dt = dt.astype(_F32)
    xg = x.reshape(batch, nc, q, groups, r, p)
    dtg = dt.reshape(batch, nc, q, groups, r)
    b = b.reshape(batch, nc, q, groups, n)
    c = c.reshape(batch, nc, q, groups, n)

    # log-decay from the chunk's start to each token, token included
    acs = jnp.cumsum(dtg * a.astype(_F32).reshape(groups, r), axis=2)
    acs_t = jnp.moveaxis(acs, 2, -1)                      # (B, C, g, r, q)
    xdt = (xg.astype(_F32) * dtg[..., None]).astype(dtype)

    # inside a chunk: (C B^T o L) (dt x)
    cb = jnp.einsum("zcqgn,zckgn->zcgqk", c, b, preferred_element_type=_F32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = _exp_where(causal, acs_t[..., :, None] - acs_t[..., None, :])
    m = (cb[:, :, :, None] * decay).astype(dtype)         # (B, C, g, r, q, k)
    y = jnp.einsum("zcgrqk,zckgrp->zcqgrp", m, xdt,
                   preferred_element_type=_F32)

    # what each chunk leaves behind: B^T (decay to the chunk's end o dt x)
    to_end = jnp.exp(acs[:, :, -1:] - acs)                # (B, C, q, g, r)
    left = jnp.einsum(
        "zckgn,zckgrp->zcgrpn", b,
        (xdt.astype(_F32) * to_end[..., None]).astype(dtype),
        preferred_element_type=_F32)

    # across chunks, float32: the state entering chunk i is the sum over
    # earlier chunks j of (decay from the end of j to the start of i) x left_j
    total = jnp.moveaxis(acs[:, :, -1], 1, -1)            # (B, g, r, C)
    upto = jnp.cumsum(total, axis=-1)
    start = upto - total                                  # log-decay before i
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)
    carry = _exp_where(earlier, start[..., :, None] - upto[..., None, :])
    entering = jnp.einsum("zgrij,zjgrpn->zigrpn", carry, left,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + jnp.einsum("zcqgn,zcgrpn->zcqgrp", c, entering.astype(dtype),
                       preferred_element_type=_F32) * jnp.exp(acs)[..., None]

    y = y + xg.astype(_F32) * d.astype(_F32).reshape(groups, r)[..., None]
    return y.astype(dtype).reshape(batch, s + pad, heads, p)[:, :s]


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d: jax.Array) -> jax.Array:
    """The recurrence one token at a time, float32 throughout (arguments
    as ``ssd_chunked``'s): ``lax.scan`` over positions carrying ``H``
    ``(batch, heads, head_dim, state)``.  For tests."""
    batch, _, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))
    a, d = a.astype(_F32), d.astype(_F32)

    def token(h, inputs):
        x_t, dt_t, b_t, c_t = inputs       # (B, h, p), (B, h), (B, g, n) x 2
        b_t, c_t = (jnp.repeat(t, r, axis=1) for t in (b_t, c_t))
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("zhpn,zhn->zhp", h, c_t,
                         precision=jax.lax.Precision.HIGHEST) + d[:, None] * x_t
        return h, y_t

    _, y = jax.lax.scan(
        token, jnp.zeros((batch, heads, p, n), _F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
