"""Paged (block-table) decode attention as a Pallas TPU kernel.

Reference design: vLLM's PagedAttention (SOSP'23) mapped onto the TPU
grid model, next to the contiguous flash kernel in ``attention.py``.
The KV cache is not one contiguous ``(B, S, h, d)`` tensor but a pool
of fixed-size blocks ``(num_blocks, h, block_size, d)`` — head-major
pages, so both in-kernel matmuls batch over a LEADING head dimension
(the form the TPU compiler accepts) and each page's trailing
``(block_size, d)`` dims land on the (sublane, lane) tiling; each sequence
owns a *block table* — the list of physical block ids holding its
context in order.  A decode step computes attention of ONE query token
per sequence against that sequence's gathered context:

- Grid ``(batch, kv_pages)``.  The page dimension is sequential on TPU
  and carries the online-softmax running stats ``(m, l)`` plus the
  output accumulator in VMEM scratch, exactly like the flash kernel's
  kv-block dimension.
- The gather is expressed through the BlockSpec index map: block tables
  and context lengths ride as SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``), so the index map for the k/v
  blocks reads ``block_tables[b, i]`` — the DMA engine fetches physical
  block ``bt[b, i]`` while the previous page computes.  No materialized
  contiguous copy of the context ever exists.
- Ragged tails: ``context_lens[b]`` masks positions at and past the
  sequence's length inside its last (partial) block with the finite
  ``NEG_INF`` the flash kernel uses; block-table entries past the last
  live page are skipped entirely with ``pl.when`` (their table entries
  may be arbitrary padding).
- ``window=w`` restricts attention to the TRAILING ``w`` positions of
  the context (sliding-window attention).  ``window=1`` degenerates to
  an exact gather of the last position's value row — softmax over a
  single element is exactly 1.0 in floating point, so the output is
  bitwise the stored ``v`` row.  The serving engine's paged decode mode
  (serve/tpu_replica.py) leans on precisely that to keep greedy chains
  bitwise-pinned while the block-table data path does the real work.

Like every op in this package the kernel runs in pallas interpret mode
off-TPU, so the same code path is tested on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, _LOG2E, _interpret_default


def paged_attention_reference(q: jax.Array, k_cache: jax.Array,
                              v_cache: jax.Array, block_tables,
                              context_lens, *,
                              sm_scale: Optional[float] = None,
                              window: int = 0) -> jax.Array:
    """Pure-XLA oracle: gather each sequence's context contiguously via
    its block table, then plain softmax attention.  q: ``(B, h, d)``;
    caches ``(num_blocks, h, block_size, d)``; returns ``(B, h, d)``."""
    import numpy as np

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    qh = np.asarray(q, np.float32)
    kc = np.asarray(k_cache, np.float32)
    vc = np.asarray(v_cache, np.float32)
    bt = np.asarray(block_tables)
    cl = np.asarray(context_lens)
    bs = kc.shape[2]
    out = np.zeros_like(qh)
    for b in range(qh.shape[0]):
        n = int(cl[b])
        pages = bt[b, : -(-n // bs)]
        k = np.concatenate(list(kc[pages]), axis=1)[:, :n]  # (h, n, d)
        v = np.concatenate(list(vc[pages]), axis=1)[:, :n]
        lo = max(0, n - window) if window else 0
        k, v = k[:, lo:], v[:, lo:]
        s = np.einsum("hd,hkd->hk", qh[b], k) * sm_scale
        s -= s.max(-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hk,hkd->hd", p, v)
    return jnp.asarray(out)


def _paged_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, block_size, window):
    b, i = pl.program_id(0), pl.program_id(1)
    npages = pl.num_programs(1)
    ctx = cl_ref[b]
    start = jnp.maximum(ctx - window, 0) if window else 0

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_lo = i * block_size
    # A page is live iff it overlaps [start, ctx): pages past the
    # context hold arbitrary padding table entries and are skipped.
    live = (page_lo < ctx) & (page_lo + block_size > start)

    # A float32 cache gets float32 arithmetic: the MXU's default rounds
    # float32 operands to bfloat16, which would break the window=1
    # bitwise-gather identity on the chip.
    precision = (jax.lax.Precision.HIGHEST
                 if k_ref.dtype == jnp.float32 else None)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                   # (h, 1, d), pre-scaled
        k = k_ref[0]                                   # (h, bs, d)
        v = v_ref[0]
        # Head-batched matmuls with the batch dimension LEADING on both
        # operands and a (unit) non-contracting query dimension: the
        # only dot_general form Mosaic's TPU lowering accepts.
        s = jnp.einsum("hqd,hkd->hqk", q, k, precision=precision,
                       preferred_element_type=jnp.float32)  # (h, 1, bs)
        pos = page_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where((pos >= start) & (pos < ctx), s, NEG_INF)
        m_prev = m_scr[...]                            # (h, 1, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp2(m_prev - m_next)
        p = jnp.exp2(s - m_next)                       # (h, 1, bs)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            "hqk,hkd->hqd", p.astype(v.dtype), v, precision=precision,
            preferred_element_type=jnp.float32)        # (h, 1, d)
        m_scr[...] = m_next

    @pl.when(i == npages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    sm_scale: Optional[float] = None, window: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention over a paged KV cache.

    q: ``(B, h, d)`` — one query token per sequence.
    k_cache/v_cache: ``(num_blocks, h, block_size, d)`` physical pool.
    block_tables: ``(B, max_pages)`` int32 — per-sequence physical block
    ids in context order; entries past ``ceil(context_len/block_size)``
    may be arbitrary valid indices (padding).
    context_lens: ``(B,)`` int32, each >= 1.
    window: attend only to the trailing ``window`` positions (0 = all).
    Returns ``(B, h, d)``.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    B, h, d = q.shape
    bs = k_cache.shape[2]
    max_pages = block_tables.shape[1]
    # Pre-scale into the log2 domain like the flash kernel: the hot loop
    # then uses exp2 directly and the per-tile scale multiply vanishes.
    qs = (q * (sm_scale * _LOG2E)).astype(q.dtype)[:, :, None, :]
    bt = jnp.asarray(block_tables, jnp.int32)
    cl = jnp.asarray(context_lens, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, h, 1, d), lambda b, i, bt_, cl_: (b, 0, 0, 0)),
            pl.BlockSpec((1, h, bs, d),
                         lambda b, i, bt_, cl_: (bt_[b, i], 0, 0, 0)),
            pl.BlockSpec((1, h, bs, d),
                         lambda b, i, bt_, cl_: (bt_[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, 1, d),
                               lambda b, i, bt_, cl_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, 1), jnp.float32),
            pltpu.VMEM((h, 1, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_size=bs, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, 1, d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt, cl, qs, k_cache, v_cache)[:, :, 0, :]
