"""Flash attention as a Pallas TPU kernel (fwd + bwd), with an XLA reference.

Design (standard memory-efficient attention, mapped to the TPU grid model):

- Layout: kernels run on ``(batch, heads, seq, head_dim)`` so every block's
  minor two dims are ``(block_seq, head_dim)`` — Mosaic requires the minor
  dims of a block to be (8, 128)-tile friendly or equal to the array dims;
  the model-side ``(b, s, h, d)`` tensors are transposed at the call
  boundary (XLA fuses the transpose into neighbouring ops).
- Forward: grid ``(batch, heads, q_blocks, kv_blocks)``.  The last grid
  dimension is sequential on TPU, so softmax running stats ``(m, l)`` and the
  output accumulator live in VMEM scratch that persists across kv iterations;
  the normalized output and the logsumexp are written on the last kv block.
- The logsumexp residual is lane-replicated to ``(b, h, s, LANES)`` — a 1D
  row per q position cannot be expressed as a legal minor block shape, so
  stats ride in full vector registers (the layout jax's own TPU
  flash-attention kernel uses for its ``l``/``m`` outputs).
- Backward: two kernels (the classic split): one accumulates ``dk, dv`` with
  grid ``(b, h, kv_blocks, q_blocks)``, one accumulates ``dq`` with grid
  ``(b, h, q_blocks, kv_blocks)``; both recompute ``p = exp(s - lse)`` from
  the saved per-row logsumexp instead of materializing the S x S matrix.
- Causal blocks that are fully masked are skipped with ``pl.when`` so the
  kernel does ~half the FLOPs at long sequence.
- Accumulation is f32 regardless of input dtype (bf16 inputs hit the MXU).

The reference framework has no counterpart (Ray core has no tensor ops —
SURVEY.md §5); this op is the compute leaf that the SP layer (ring/ulysses)
and the model family build on.  On non-TPU backends the kernels run in
pallas interpret mode, so the same code path is tested on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite "minus infinity": keeps exp() NaN-free on masked rows
_LANES = 128     # TPU lane width; stats are lane-replicated
# Softmax runs in the log2 domain: q is pre-scaled by sm_scale*log2(e)
# outside the kernel, so the hot loop uses exp2 directly (the VPU's
# native transcendental; exp(x) lowers to exp2(x*log2e) anyway) and the
# per-element scale multiply disappears from the (bq, bk) tile.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

# Tuned on TPU v5e: large blocks amortize grid overhead (the d=64
# contraction underfills the MXU, so throughput comes from big output
# tiles); _fit_block shrinks them for short sequences.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params(interpret):
    if interpret:
        return None
    # First three grid dims are embarrassingly parallel; the innermost
    # carries the running softmax state and must stay sequential.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0, kv_offset: int = 0) -> jax.Array:
    """Pure-XLA multi-head attention, the numerics oracle for every kernel.

    ``q_offset``/``kv_offset`` are global positions of element 0 of the q/kv
    chunks — used by ring attention where each device holds a seq slice.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qi = q_offset + jnp.arange(q.shape[1])[:, None]
        ki = kv_offset + jnp.arange(k.shape[1])[None, :]
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _causal_mask(s, qi, ki, block_q, block_k):
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: block is live iff its last q row can see its first kv column.
    live = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                                      # (bq, d)
        k = k_ref[0, 0]                                      # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev = m_scr[...]                          # (bq, LANES) replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (bq, 1)
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp2(m_prev - m_next)
        p = jnp.exp2(s - m_next[:, :1])
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), m_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log2(l)   # log2-domain lse


def _fwd_call(qt, kt, vt, causal, block_q, block_k, interpret):
    """qt/kt/vt: (b, h, s, d); qt PRE-SCALED by sm_scale*log2e.  Returns
    (o_t, lse) with o_t (b, h, sq, d) and lse (b, h, sq, LANES)
    lane-replicated f32 in the log2 domain."""
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, qt.dtype),
            jax.ShapeDtypeStruct((b, h, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return o, lse


# ---------------------------------------------------------------- backward

def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr,
                 *, causal, block_q, block_k):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                   # (bq, LANES)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp2(s - lse[:, :1])                          # (bq, bk)
        # Grad matmuls in the INPUT dtype (bf16 on TPU): the MXU runs
        # bf16 natively; the old f32 operands forced multi-pass matmuls.
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, :1])).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        # q arrives pre-scaled by c = sm_scale*log2e; the true gradient
        # is sm_scale * ds^T @ q_unscaled = ln2 * ds^T @ (q*c).
        dk_ref[0, 0] = (dk_scr[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k):
    # sm_scale is applied once at finalize: dL/dq_orig = sm_scale * ds@k.
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp2(s - lse[:, :1])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, :1])).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_call(qt, kt, vt, ot, lse, dot, sm_scale, causal, block_q, block_k,
              interpret):
    """All tensors (b, h, s, d); lse (b, h, sq, LANES).  Returns transposed
    grads (dqt, dkt, dvt)."""
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    delta = jnp.sum(ot.astype(jnp.float32) * dot.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (b, h, sq, 1)
    delta = jnp.broadcast_to(delta, (b, h, sq, _LANES))

    q_i = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    q_j = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    k_i = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    k_j = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    row_i = pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0))
    row_j = pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h_, i, j: (b_, h_, j, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, pl.cdiv(sk, block_k), pl.cdiv(sq, block_q)),
        in_specs=[q_j, k_i, k_i, q_j, row_j, row_j],
        out_specs=[k_i, k_i],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_dkv",
    )(qt, kt, vt, dot, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, h, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
        in_specs=[q_i, k_j, k_j, q_i, row_i, row_i],
        out_specs=q_i,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_dq",
    )(qt, kt, vt, dot, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------- public

def _to_bhsd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    qs = (q * (sm_scale * _LOG2E)).astype(q.dtype)
    o, _ = _fwd_call(_to_bhsd(qs), _to_bhsd(k), _to_bhsd(v), causal,
                     block_q, block_k, interpret)
    return _to_bhsd(o)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    qs = (q * (sm_scale * _LOG2E)).astype(q.dtype)
    qt, kt, vt = _to_bhsd(qs), _to_bhsd(k), _to_bhsd(v)
    ot, lse = _fwd_call(qt, kt, vt, causal, block_q, block_k, interpret)
    return _to_bhsd(ot), (qt, kt, vt, ot, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, res, do):
    qt, kt, vt, ot, lse = res
    dqt, dkt, dvt = _bwd_call(qt, kt, vt, ot, lse, _to_bhsd(do), sm_scale,
                              causal, block_q, block_k, interpret)
    return _to_bhsd(dqt), _to_bhsd(dkt), _to_bhsd(dvt)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Memory-efficient MHA.  q: (b, sq, h, d); k/v: (b, sk, h, d).

    Supports grouped-query attention: if k/v have fewer heads than q and
    ``h % h_kv == 0``, kv heads are repeated (XLA fuses the broadcast).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    from ray_tpu.ops.layers import repeat_kv_heads
    k, v = repeat_kv_heads(q, k, v)
    # The kernels have no partial-block masking: blocks must tile the
    # sequence exactly.  Shrink to a fitting power-of-two block; if none
    # >= 8 exists, use the XLA reference (correct, O(S^2) memory).
    block_q = _fit_block(block_q, q.shape[1])
    block_k = _fit_block(block_k, k.shape[1])
    if block_q is None or block_k is None:
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret)


def _fit_block(block: int, seq: int) -> Optional[int]:
    block = min(block, seq)
    while block >= 8:
        if seq % block == 0:
            return block
        block //= 2
    return None
