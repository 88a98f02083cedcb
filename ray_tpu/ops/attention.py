"""Flash attention as a Pallas TPU kernel (fwd + bwd), with an XLA reference.

Design (standard memory-efficient attention, mapped to the TPU grid model):

- Layout: a block's minor two dims are ``(block_seq, head_dim)`` — Mosaic
  wants them (8, 128)-tile friendly or equal to the array's —, and the
  kernels take their operands in ONE OF TWO ADDRESSINGS, chosen from the
  call's shapes alone (``_in_place``; ``_grid_and_specs``).  Where a head
  fills whole lane blocks (``d % 128 == 0`` for q/k's head and v's) they
  read q, k, v — and write o, dq, dk, dv — IN the model's own ``(b, s,
  heads x d)``, the free reshape of the ``(b, s, h, d)`` the mixer holds: a
  head is the lane block ``(rows, d)`` at ``(b_, tile, head)``, 256 B a row
  of 128 bf16 lanes, rows ``heads x d`` elements apart; Mosaic's DMA hides
  the stride (PERF.md §6, PR 54: the kernels' sum within 1.4 % of the same
  calls on contiguous heads, and under the parent's).  Else (a head of 64 lanes, a latent mixer's
  192 / 128, the tests' tiny heads) the operands are turned round to
  ``(b, heads, s, d)`` at the call's boundary.  XLA does NOT fuse those
  transposes into its neighbours: the traces read 16-22 ms a step of
  copies round the calls in the cells of 128-lane heads, which is why the
  first addressing exists.  The per-row stats are the kernels' own in both.
- Grouped queries: k and v come with their own ``h_kv`` heads and nothing
  repeats them.  ``flash_fwd`` reads KV head ``h // rep`` by the index
  map; the backward kernel runs over the KV heads, its third axis walking
  the ``rep`` q heads of the group and each head's q tiles, so ``dk`` /
  ``dv`` are summed over the group in float32 in VMEM and leave the
  kernel once a KV head.
- Forward: grid ``(batch, heads, q_blocks, kv_blocks)``.  The last grid
  dimension is sequential on TPU, so softmax running stats ``(m, l)`` and the
  output accumulator live in VMEM scratch that persists across kv iterations;
  the normalized output and the logsumexp are written on the last kv block.
- The forward writes its logsumexp lane-replicated ``(b, h, s, LANES)``:
  per-row stats of a ``(q rows, k columns)`` tile are COLUMNS, and a
  column rides in full vector registers (the layout jax's own TPU
  flash-attention kernel uses for its ``l``/``m`` outputs).  ONE lane of
  it, a float a row, is what the checkpoint keeps and the backward takes.
- Backward: ONE kernel (named ``flash_dkv``: it is that kernel with a
  third output), grid ``(b, h_kv, rep x q_blocks, kv_blocks)``.  What
  crosses the call is q, k, v, o, do and ``lse`` ALONE, a float a row:
  ``delta``, the float a query row ``sum_d o do``, is made inside the
  kernel once a q tile from the ``o`` and ``do`` tiles it holds (summed in
  float32 on the vector unit), so no array of it and no float32 ``o x do``
  exists outside (XLA's own ``delta`` over ONE row of 16384 tokens read in
  place was four passes over a float32 array the size of two q's: PERF.md
  §6, PR 77).  For each live sub-tile it recomputes ``p = exp2(s - lse)``
  from the saved per-row logsumexp instead of materializing the S x S
  matrix, makes ``dP`` and ``ds = p (dP - delta)`` ONCE, and accumulates
  all three gradients from them: ``dv += p^T do``, ``dk += ds^T q``,
  ``dq += ds k`` — five products
  a pair where the classic split (a dk/dv kernel and a dq kernel, each
  with its own scores and ``dP``) runs seven.  One grid cannot visit both
  a q tile's and a kv tile's accumulator consecutively: ``dq`` is what
  the LAST axis gathers (one q tile of float32 scratch, written out once
  a q tile), and ``dk`` / ``dv`` stay in VMEM for a KV head's WHOLE
  sequence, ``sk x (d + dv)`` float32 whatever the group's size, and are
  written out once a KV head (``_check_resident`` is the one limit this
  adds).
  The kernel keeps its scores TRANSPOSED, ``s^T = k q^T`` with the q rows
  along the lanes, so ``dv``'s and ``dk``'s products are plain ones, its
  per-row stats are rows — ``lse`` comes in as ``(b, h, 1, sq)``, ``delta``
  is a row of scratch; nothing is broadcast to 128 lanes for it — and
  ``dq``'s is the one product that contracts the tile's FIRST dimension.
  It gathers turned round, ``dq^T += k^T ds^T``
  as ``(d, block_q)``, so what Mosaic turns is a strip of k and not the
  tile, and is turned back once a q tile on its way out.
- Causal schedule: a grid step FETCHES a large tile and the kernel walks
  it in COMPUTE sub-tiles, each dead (no code runs), interior (no mask) or
  on an edge (masked); grid steps whose whole tile is dead name the block
  their neighbour holds, so nothing is copied for them.  With the sizes
  ``choose_tiles`` picks the kernels compute 1.06 times the causal pairs at
  s=4096 and 1.25 times at s=512 (``causal_tile_counts``), where whole
  512 x 1024 tiles computed 1.25 and 2.0 times.
- A WINDOW (``flash_attention(window=w)``: query i sees key j iff ``0 <= i
  - j < w``) gives the schedule a second, FAR edge beside the diagonal: a
  sub-tile wholly older than the window is dead too, one that straddles
  the far edge takes a mask of its own, and each q tile has a first live
  kv tile as it has a last one.  The windowed calls are named
  ``flash_fwd_win`` / ``flash_dkv_win``; without a window nothing of this
  is traced and the kernels are what they were.  At s=8192, w=4096 they
  compute 1.06 times the 25.17 M pairs the window leaves of the 33.56 M
  causal ones.
- Accumulation is f32 regardless of input dtype (bf16 inputs hit the MXU).

The reference framework has no counterpart (Ray core has no tensor ops —
SURVEY.md §5); this op is the compute leaf that the SP layer (ring/ulysses)
and the model family build on.  On non-TPU backends the kernels run in
pallas interpret mode, so the same code path is tested on CPU.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
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

# Fetch tile (what one grid step copies into VMEM) and compute sub-tile,
# measured on TPU v5e in bf16 at d=128 (PERF.md §6, PR 24) and at a latent
# mixer's 192 / 128 (PR 35).  Under the causal mask the largest tile wins:
# fewer ~0.35 us grid steps, fewer running-softmax updates, longer
# contractions, and the sub-tile walk keeps what is computed above the
# diagonal small whatever the tile.  Without a mask the tile stays what the
# kernels always used.  What a large tile costs is CODE: one call over a
# whole 2048 x 2048 tile is straight-line code, and a dk/dv kernel's four
# products at 192 / 128 ran at half speed until its interior tile became a
# loop over strips (``_bwd_kernel``, which has five).
MAX_BLOCK = 2048                   # rows of a causal fetch tile, q and kv
UNMASKED_BLOCK = (512, 1024)       # (q, kv) rows of a non-causal one
_BLOCK_BYTES = 2 * 1024 * 1024     # one operand's block: bounds rows by d
SUB_TILES = (256, 128)             # compute sub-tile widths, widest first
MAX_EXECUTED = 1.25                # executed / causal pairs a width may cost


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


_VMEM_LIMIT = 100 * 1024 * 1024
# What a KV head's whole-sequence dk / dv may take of it in the backward
# kernel; the rest is the fetched blocks, dq and a strip's temporaries
# (compiled for a v5e: 80 MiB fits at 128-wide bf16 and f32 heads, not at
# 256-wide f32 ones, whose blocks are 2 MB each).
_RESIDENT_BYTES = 64 * 1024 * 1024


def _compiler_params(interpret, sequential=1):
    if interpret:
        return None
    # The leading grid dims are embarrassingly parallel; the last
    # ``sequential`` carry state in VMEM scratch (the forward's running
    # softmax over the kv tiles; the backward's dk / dv over a KV head's
    # q tiles as well) and must stay in order.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (4 - sequential)
        + ("arbitrary",) * sequential,
        vmem_limit_bytes=_VMEM_LIMIT)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0, kv_offset: int = 0,
                  window: Optional[int] = None,
                  block: Optional[int] = None) -> jax.Array:
    """Pure-XLA multi-head attention, the numerics oracle for every kernel.

    Under ``causal`` query ``i`` sees key ``j`` iff ``j <= i`` (the near
    edge, the diagonal) and, with a ``window``, iff also ``i - j < window``
    (the far edge: the query itself and the ``window - 1`` keys before it).
    ``q_offset``/``kv_offset`` are global positions of element 0 of the q/kv
    chunks — used by ring attention where each device holds a seq slice.
    Under ``block`` the rows are TWO STREAMS of one sequence (``block_mask``,
    from the definition, dense).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if window is not None and not causal:
        raise ValueError("a window is the causal mask's far edge")
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if block is not None:
        _check_block(block, causal, window, q.shape[1], k.shape[1])
        s = jnp.where(block_mask(q.shape[1] // 2, block), s, NEG_INF)
    elif causal:
        qi = q_offset + jnp.arange(q.shape[1])[:, None]
        ki = kv_offset + jnp.arange(k.shape[1])[None, :]
        seen = qi >= ki if window is None else (qi >= ki) & (qi - ki < window)
        s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def block_mask(length: int, block: int, first: int = 0,
               rows: Optional[int] = None) -> jax.Array:
    """The block-diffusion rule, dense, from its four cases: ``(2 length, 2
    length)`` booleans over the rows ``[noised ; clean]`` of ONE sequence
    of ``length`` positions in blocks of ``block`` (position ``i`` lies in
    block ``i // block``; a noised row and its clean copy share a position)
    — or its ``rows`` rows from ``first`` on.  A clean row sees the clean
    keys of its own block and of every earlier one; a noised row the clean
    keys of STRICTLY earlier blocks and the noised keys of its own block,
    both directions; no clean row sees a noised key."""
    at = jnp.arange(2 * length)
    clean, b = at >= length, (at % length) // block
    here = slice(first, None if rows is None else first + rows)
    (rc, cc), (rb, cb) = ((x[here, None], x[None, :]) for x in (clean, b))
    return jnp.where(rc, cc & (cb <= rb), jnp.where(cc, cb < rb, cb == rb))


def _check_block(block, causal, window, sq, sk):
    if not causal or window is not None or sq != sk or sq % (2 * block):
        raise ValueError(
            f"block {block}: the block rule is over two streams of one "
            f"sequence, [noised ; clean], q and k alike ({sq}, {sk} rows), "
            "each a whole number of blocks, under no window")


# ------------------------------------------------------ causal tile schedule
#
# A FETCH tile ``(block_q, block_k)`` is what one grid step copies into
# VMEM; the kernels walk it in COMPUTE sub-tiles ``(sub_q, sub_k)``.
# With ``off`` = (first kv column) - (first q row) of a sub-tile, row r of
# it sees its column c iff ``r - c >= off`` (the NEAR edge, the diagonal)
# and, under a window of ``w``, iff also ``r - c < off + w`` (the FAR edge):
#   dead      off >= sub_q           its last q row is before its first column
#             off <= 1 - sub_k - w   its first row is past the window of its
#                                    last column
#   interior  sub_q - w <= off <= 1 - sub_k   every pair is seen
#   edge      otherwise: near (off > 1 - sub_k, the diagonal), far
#             (off < sub_q - w) or both; the only kinds that need a mask
# Causal positions are top-left aligned (row r sees column c iff r >= c),
# as ``mha_reference`` has them with zero offsets.

def _tile_kind(off, sub_q, sub_k, window=None):
    """(interior, on an edge) of a sub-tile: Python bools for a Python int
    ``off`` (with a window always), traced scalars otherwise.  Neither
    holds for a dead one.  Without a window the one edge is the diagonal."""
    if window is None:
        return off <= 1 - sub_k, (off > 1 - sub_k) & (off < sub_q)
    edges = _edges(off, sub_q, sub_k, window)
    return edges == (False, False), edges is not None and any(edges)


def _edges(off: int, sub_q, sub_k, window):
    """(near, far): the edges a live sub-tile straddles; None for a dead
    one."""
    if off >= sub_q or (window is not None and off <= 1 - sub_k - window):
        return None
    return off > 1 - sub_k, window is not None and off < sub_q - window


def _last_live_k(i, block_q, block_k):
    """Last kv tile that q tile ``i`` sees any column of."""
    return (i * block_q + block_q - 1) // block_k


def _first_live_k(i, block_q, block_k, window):
    """First kv tile that q tile ``i`` sees any column of: the one that
    holds the oldest key of its first row's window."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def causal_tile_counts(sq: int, sk: int, block_q: int, block_k: int,
                       sub_q: int, sub_k: int, causal: bool = True,
                       window: Optional[int] = None) -> dict:
    """What the schedule executes for one (batch, head), from shapes
    alone: the number of compute sub-tiles of each kind (``diagonal``:
    those on an edge, the diagonal or a window's far one), the (q, k)
    pairs the live ones compute, and the pairs the mask leaves
    (``causal_pairs``; under a ``window`` those inside it, ``sq * sk``
    without a mask).  Sub-tiles never span fetch tiles, so the fetch tile
    only matters where it cuts a sub-tile short."""
    sub_q, sub_k = min(sub_q, block_q), min(sub_k, block_k)
    counts = {"dead": 0, "interior": 0, "diagonal": 0}
    for q0 in range(0, sq, sub_q):
        for k0 in range(0, sk, sub_k):
            interior, diagonal = (True, False) if not causal else (
                _tile_kind(k0 - q0, sub_q, sub_k, window))
            counts["interior" if interior else
                   "diagonal" if diagonal else "dead"] += 1
    live = counts["interior"] + counts["diagonal"]
    counts["executed_pairs"] = live * sub_q * sub_k
    rows = min(sq, sk)   # row r sees min(r + 1, sk) columns
    if not causal:
        counts["causal_pairs"] = sq * sk
    elif window is None:
        counts["causal_pairs"] = rows * (rows + 1) // 2 + (sq - rows) * sk
    else:   # ... of which the last ``window`` at most
        counts["causal_pairs"] = sum(
            max(0, min(r, sk - 1) - max(0, r - window + 1) + 1)
            for r in range(sq))
    return counts


def _walk_tile(causal, off, tiles, body, strips, window=None):
    """Run ``body(q_slice, k_slice, mask)`` over the live part of the
    fetched tile whose first column minus first row is ``off``.

    A fetched tile is dead (nothing runs), interior as a whole (one call
    over all of it, no mask) or straddles an edge: the diagonal or, under
    a ``window``, the far edge.  ``off`` is a multiple of gcd(block_q,
    block_k), so the straddling offsets are few and known when the kernel
    is traced: each gets ONE branch of straight-line code, which the
    scheduler overlaps where per-sub-tile branches would serialise it.
    In it the tile is cut in strips of sub-tiles along ``strips`` ("q":
    one per ``sub_q`` rows, for kernels that accumulate per q row; "k":
    one per ``sub_k`` columns).  A strip's live sub-tiles are adjacent and
    run as ONE call; its dead ones run no code; those on an edge lie at
    its ends, and ``mask`` = (axis, segments) says which part of the call
    needs which test: ``segments`` cuts the call's columns (axis 1, "q"
    strips) or rows (axis 0, "k" strips) into ``(size, lo, hi)``, row r of
    a segment seeing its column c iff ``lo <= r - c <= hi`` (a bound that
    is None is not tested; a segment with neither is interior).  ``mask``
    is None where every sub-tile of the strip is interior."""
    block_q, block_k, sub_q, sub_k = tiles
    whole = functools.partial(body, pl.ds(0, block_q), pl.ds(0, block_k), None)
    if not causal:
        return whole()
    by_q = strips == "q"
    # (length, step) across the strips and along one
    across, along = (((block_q, sub_q), (block_k, sub_k)) if by_q else
                     ((block_k, sub_k), (block_q, sub_q)))

    def walk(off):
        for s0 in range(0, *across):
            # the live sub-tiles of the strip, as runs of one kind:
            # [start, size, needs the near test, needs the far test]
            runs = []
            for x0 in range(0, *along):
                a, t = (s0, x0) if by_q else (x0, s0)
                tests = _edges(off + t - a, sub_q, sub_k, window)
                if tests is None:
                    continue
                if runs and tuple(runs[-1][2:]) == tests:
                    runs[-1][1] += along[1]
                else:
                    runs.append([x0, along[1], *tests])
            if not runs:
                continue
            first = runs[0][0]
            extent = runs[-1][0] + runs[-1][1] - first
            segments = []
            for x0, size, near, far in runs:
                # first column minus first row of this run of sub-tiles
                run_off = off + (x0 - s0 if by_q else s0 - x0)
                segments.append((size, run_off if near else None,
                                 run_off + window - 1 if far else None))
            masked = any(lo is not None or hi is not None
                         for _, lo, hi in segments)
            if by_q:
                qs, ks = pl.ds(s0, sub_q), pl.ds(first, extent)
            else:
                qs, ks = pl.ds(first, extent), pl.ds(s0, sub_k)
            body(qs, ks, (int(by_q), tuple(segments)) if masked else None)

    if isinstance(off, int):     # a one-tile grid: decided while tracing
        return walk(off)
    step = math.gcd(block_q, block_k)
    if window is None:
        pl.when(off <= -block_k)(whole)  # the next multiple of step straddles
        straddling = range(step - block_k, block_q, step)
    else:
        # live: 1 - block_k - window < off < block_q; of those, interior as
        # a whole: block_q - window <= off <= -block_k
        oldest = block_q - window
        if oldest <= -block_k:
            pl.when((off <= -block_k) & (off >= oldest))(whole)
        live = range(((1 - block_k - window) // step + 1) * step, block_q,
                     step)
        straddling = [o for o in live if not oldest <= o <= -block_k]
    for o in straddling:
        pl.when(off == o)(functools.partial(walk, o))


def _scores(q, k, mask, transposed=False, rule=None):
    """f32 ``q @ k^T`` of one strip, or ``k @ q^T`` (the q rows along the
    lanes) if ``transposed``.  ``mask`` = (axis, segments) as ``_walk_tile``
    hands it: the k columns (axis 1) or the q rows (axis 0) in segments
    ``(size, lo, hi)``, row r of one seeing its column c iff ``lo <= r - c
    <= hi``.  ``rule`` = (shift, strict), the BLOCK rule: a segment's rows
    and columns count in blocks of ``2 ** shift`` (every ``lo`` is a whole
    number of them) and row r sees column c iff ``(r >> shift) - (c >>
    shift) >= (lo >> shift) + strict`` — the whole own block with ``strict``
    0, strictly earlier blocks with 1 (a traced scalar will do)."""
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if mask is None:
        return s
    axis, segments = mask
    rows = int(transposed)           # the axis of s the q rows lie along
    cut_axis = axis ^ rows
    ends = list(itertools.accumulate(size for size, _, _ in segments))
    parts = [jax.lax.slice_in_dim(s, end - size, end, axis=cut_axis)
             for end, (size, _, _) in zip(ends, segments)]
    for i, (_, lo, hi) in enumerate(segments):
        if lo is None and hi is None:
            continue
        part = parts[i]
        if rule is None:
            diff = _block_diff(part.shape, rows, 0)
        else:
            diff, lo = _block_diff(part.shape, rows, rule[0]), (
                lo >> rule[0]) + rule[1]
        seen = (diff >= lo if hi is None else diff <= hi if lo is None
                else (diff >= lo) & (diff <= hi))
        parts[i] = jnp.where(seen, part, NEG_INF)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, cut_axis)


def _block_diff(shape, rows: int, shift: int):
    """Row less column of every element of a tile of ``shape`` whose q rows
    lie along axis ``rows``, each counted in blocks of ``2 ** shift``."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, rows)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows)
    if shift:
        r, c = (jax.lax.shift_right_arithmetic(x, jnp.int32(shift))
                for x in (r, c))
    return r - c


def _own_block(s, shift: int):
    """``s``, the scores of a run of rows against THEIR OWN positions' keys
    (a square on the diagonal), less every pair of two different blocks of
    ``2 ** shift``."""
    return jnp.where(_block_diff(s.shape, 0, shift) == 0, s, NEG_INF)


def _tile_offset(causal, qi, ki, tiles, grid_qk):
    """First column minus first row of grid tile (qi, ki): a Python 0 on
    a one-tile grid, and unused without a mask."""
    if not causal or grid_qk == (1, 1):
        return 0
    return ki * tiles[1] - qi * tiles[0]


# ---------------------------------------------------------------- forward

def _streams(bd, qi, nq):
    """Under the block rule a kernel's q-tile axis walks the NOISED stream's
    ``nq`` tiles, then the clean stream's: ``(the tile within its stream,
    whether it is the noised one's, the rule _scores takes)``; without it
    the axis as it is."""
    if bd is None:
        return qi, None, None
    noised = qi < nq
    return jax.lax.rem(qi, nq), noised, (bd[0], noised.astype(jnp.int32))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, tiles, grid_qk,
                window=None, sel_ref=None, bd=None):
    # ``sel_ref``: a mask that is DATA (``ops/sparse_attention.py``), the
    # tile's ``(q rows, k columns)`` of it, nonzero where the pair is live.
    # ``bd`` = (shift, kn_ref, vn_ref): the block rule (``flash_fwd_bd``);
    # k_ref / v_ref walk the CLEAN keys, kn_ref / vn_ref hold the noised
    # stream's keys and values at this q tile's own positions.
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    qi, noised, rule = _streams(bd, qi, grid_qk[0])

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def softmax_step(qs, s, v):
        m_prev = m_scr[qs]                           # (sq, LANES) replicated
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (sq, 1)
        m_next = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp2(m_prev - m_next)
        p = jnp.exp2(s - m_next[:, :1])
        l_scr[qs] = l_scr[qs] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), m_prev.shape)
        acc_scr[qs] = acc_scr[qs] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[qs] = m_next

    def update(qs, ks, mask):
        s = _scores(q_ref[qs], k_ref[ks], mask, rule=rule)   # f32
        if sel_ref is not None:
            s = jnp.where(sel_ref[qs, ks] != 0, s, NEG_INF)
        softmax_step(qs, s, v_ref[ks])

    if bd is not None:
        @pl.when((ki == 0) & noised)
        def _own_blocks():
            # a noised row's own block, both directions: the noised keys at
            # its strip's own positions, before any clean key (so no row's
            # running maximum is ever that of nothing)
            for s0 in range(0, tiles[0], tiles[2]):
                qs = pl.ds(s0, tiles[2])
                softmax_step(qs, _own_block(_scores(
                    q_ref[qs], bd[1][qs], None), bd[0]), bd[2][qs])

    _walk_tile(causal, _tile_offset(causal, qi, ki, tiles, grid_qk), tiles,
               update, strips="q", window=window)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log2(l)   # log2-domain lse


def _dims(qt, kt, vt, heads):
    """``(b, h, h_kv, sq, sk, d, dv)`` of a call's operands in either
    addressing: ``heads`` is None for ``(b, heads, s, d)`` operands, q's and
    kv's head counts ``(h, h_kv)`` for ``(b, s, heads x d)`` ones."""
    if heads is None:
        (b, h, sq, d), (_, h_kv, sk, _) = qt.shape, kt.shape
        return b, h, h_kv, sq, sk, d, vt.shape[3]
    h, h_kv = heads
    (b, sq, width), sk = qt.shape, kt.shape[1]
    return b, h, h_kv, sq, sk, width // h, vt.shape[2] // h_kv


def _grid_and_specs(qt, kt, vt, causal, tiles, window=None, heads=None,
                    bd=False):
    """``(nq, nk)`` tiles of the call and the BlockSpecs of the q-side and
    kv-side operands for both grids: ``q_i, k_j, row_i`` for the forward's
    ``(b, h, q, kv)`` and ``q_t, k_t, stat_t, k_all`` for the backward's
    ``(b, h_kv, rep x q, kv)`` (``row``: per-row stats lane-replicated
    ``(b, h, sq, LANES)``, the forward's output; ``stat``: a float a row as
    rows ``(b, h, 1, sq)`` — the log-sum-exp, the one statistic that
    crosses the backward call: ``delta`` is made in the kernel from the
    ``o_t`` blocks of o and do; ``all``: a KV head's whole sequence, the
    block of ``dk`` / ``dv``, which no grid step of the head moves).

    TWO ADDRESSINGS of q, k, v, o and their gradients, one body a kernel
    (every leading dimension of a block is squeezed: a ref is ``(rows,
    width)``).  ``heads=None``: the operands are ``(b, heads, s, d)`` and
    a block is ``(rows, d)`` at ``(b_, head, tile, 0)``.  ``heads=(h,
    h_kv)``: they are the model's own ``(b, s, heads x d)`` and a head is a
    LANE BLOCK, ``(rows, d)`` at ``(b_, tile, head)``: its rows lie ``heads
    x d`` elements apart, and nothing is transposed round the call.  The
    stats are the kernels' own in both.

    A KV head serves ``rep = h // h_kv`` q heads BY THE INDEX MAP: the
    forward's grid reads kv head ``h_ // rep``; the backward's runs over
    the KV heads, and its axis ``t`` walks the group's q heads and each
    head's q tiles (q head ``g x rep + t // nq``, q tile ``t % nq``) while
    the last axis walks that q tile's kv tiles, so ``dq`` leaves the
    kernel once a ``t`` and ``dk`` and ``dv`` once a KV head.  At ``rep ==
    1`` the two grids' q and kv maps are the same.

    q and k have one head size, v (and with it o and do: ``o_i``, ``v_j``,
    ``o_t``, ``v_t``, ``v_all``) may have another; where the two are equal
    the specs are.  Under the mask a dead grid step names the block its
    nearest live step holds, which Pallas does not copy again: past the
    diagonal and, under a ``window``, before the far edge.

    ``bd``: the BLOCK rule.  The rows are two streams, ``[noised ; clean]``,
    and ``(nq, nk)`` count ONE stream's tiles: a grid's q axis is ``2 nq``
    long, the noised stream's tiles then the clean one's; its kv axis walks
    the CLEAN keys (the second half's tiles, up to the q tile's diagonal,
    for either stream), and ``kn_*`` / ``vn_*`` name the noised keys and
    values at a noised q tile's own positions (``block_q`` rows; the clean
    stream's steps keep naming the last one, which is then not copied)."""
    block_q, block_k = tiles[:2]
    _, h, h_kv, sq, sk, d, dv = _dims(qt, kt, vt, heads)
    nq, nk = sq // block_q, sk // block_k
    rep = h // h_kv
    q_tiles = nq
    if bd:
        nq, nk = nq // 2, nk // 2

        def inner_k(i, j):
            return nk + jnp.minimum(
                j, _last_live_k(jax.lax.rem(i, nq), block_q, block_k))
    elif causal:
        def inner_k(i, j):
            j = jnp.minimum(j, _last_live_k(i, block_q, block_k))
            if window is None:
                return j
            return jnp.maximum(j, _first_live_k(i, block_q, block_k, window))
    else:
        inner_k = lambda i, j: j

    # (head, tile) a grid step names, by operand and grid: (h_, i, j) of the
    # forward's, (g, t, j) of the backward's
    if rep == 1:
        kv_head = lambda h_: h_
        walk = lambda g, t: (g, t)
    else:
        kv_head = lambda h_: h_ // rep
        walk = lambda g, t: (g * rep + t // q_tiles, t % q_tiles)
    outer = lambda h_, i, j: (h_, i)
    k_inner = lambda h_, i, j: (kv_head(h_), inner_k(i, j))
    q_walk = lambda g, t, j: walk(g, t)
    k_walk = lambda g, t, j: (g, inner_k(walk(g, t)[1], j))
    whole = lambda g, t, j: (g, 0)
    own = lambda i: jnp.minimum(i, nq - 1)
    own_i = lambda h_, i, j: (kv_head(h_), own(i))
    own_t = lambda g, t, j: (g, own(walk(g, t)[1]))
    stat_at = lambda g, t, j: (walk(g, t)[0], 0, walk(g, t)[1])

    def spec(block, width, at):
        if heads is None:
            return pl.BlockSpec(
                (None, None, block, width),
                lambda b_, h_, i, j: (b_, *at(h_, i, j), 0))
        return pl.BlockSpec(
            (None, block, width),
            lambda b_, h_, i, j: (b_, *at(h_, i, j)[::-1]))

    return (nq, nk), {
        "q_i": spec(block_q, d, outer), "o_i": spec(block_q, dv, outer),
        "k_j": spec(block_k, d, k_inner), "v_j": spec(block_k, dv, k_inner),
        "row_i": pl.BlockSpec(
            (None, None, block_q, _LANES),
            lambda b_, h_, i, j: (b_, h_, i, 0)),
        "q_t": spec(block_q, d, q_walk), "o_t": spec(block_q, dv, q_walk),
        "k_t": spec(block_k, d, k_walk), "v_t": spec(block_k, dv, k_walk),
        "k_all": spec(sk, d, whole), "v_all": spec(sk, dv, whole),
        "kn_i": spec(block_q, d, own_i), "vn_i": spec(block_q, dv, own_i),
        "kn_t": spec(block_q, d, own_t), "vn_t": spec(block_q, dv, own_t),
        "stat_t": pl.BlockSpec(
            (None, None, 1, block_q),
            lambda b_, g, t, j: (b_, *stat_at(g, t, j))),
        # a data mask ``(b, sq, sk)`` in the forward's grid, and turned
        # round, ``(b, sk, sq)``, in the backward's
        "sel_i": pl.BlockSpec(
            (None, block_q, block_k),
            lambda b_, h_, i, j: (b_, i, inner_k(i, j))),
        "sel_t": pl.BlockSpec(
            (None, block_k, block_q),
            lambda b_, g, t, j: (b_, k_walk(g, t, j)[1], walk(g, t)[1])),
    }


def _kernel_name(name: str, window, sel=None, block=None) -> str:
    """The windowed calls, those under a mask that is data and those under
    the block rule carry names of their own that START with the plain ones,
    so what sums ``flash_*`` holds them and a reader can tell them apart."""
    if sel is not None:
        return name + "_dsa"
    if block is not None:
        return name + "_bd"
    return name if window is None else name + "_win"


def _with_sel(kernel, at: int):
    """``kernel`` for a call whose operand ``at`` is a data mask: the ref
    goes in by its name."""
    def call(*refs, **kw):
        return kernel(*refs[:at], *refs[at + 1:], sel_ref=refs[at], **kw)
    return call


def _with_own(kernel, at: int, shift: int):
    """``kernel`` for a call under the block rule, whose operands ``at``
    and ``at + 1`` are the noised stream's own keys and values."""
    def call(*refs, **kw):
        return kernel(*refs[:at], *refs[at + 2:],
                      bd=(shift, *refs[at:at + 2]), **kw)
    return call


def _fwd_call(qt, kt, vt, causal, tiles, interpret, window=None, heads=None,
              sel=None, block=None):
    """qt, kt, vt in either addressing (``_grid_and_specs``); qt PRE-SCALED
    by sm_scale*log2e.  Returns (o_t, lse) with o_t addressed as qt, v's
    head size wide, and lse (b, h, sq, LANES) lane-replicated f32 in the
    log2 domain.  ``sel (b, sq, sk)`` int8: a mask that is data, nonzero
    where the pair is live (the call is then ``flash_fwd_dsa``).  ``block``:
    the rows are two streams under the block rule (``flash_fwd_bd``)."""
    b, h, _, sq, _, _, dv = _dims(qt, kt, vt, heads)
    block_q = tiles[0]
    (nq, nk), specs = _grid_and_specs(qt, kt, vt, causal, tiles, window,
                                      heads, block is not None)
    o_shape = (b, h, sq, dv) if heads is None else (b, sq, h * dv)
    kernel, masks, own = _fwd_kernel, (), ()
    if sel is not None:
        kernel, masks = _with_sel(_fwd_kernel, 3), (sel,)
    if block is not None:
        kernel, own = _with_own(_fwd_kernel, 3, _shift(block)), (kt, vt)
    o, lse = pl.pallas_call(
        functools.partial(kernel, causal=causal, tiles=tiles,
                          grid_qk=(nq, nk), window=window),
        grid=(b, h, sq // block_q, nk),
        in_specs=[specs["q_i"], specs["k_j"], specs["v_j"],
                  *(specs["sel_i"] for _ in masks),
                  *(specs[n] for n in ("kn_i", "vn_i")[:len(own)])],
        out_specs=[specs["o_i"], specs["row_i"]],
        out_shape=[
            jax.ShapeDtypeStruct(o_shape, qt.dtype),
            jax.ShapeDtypeStruct((b, h, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=_kernel_name("flash_fwd", window, sel, block),
    )(qt, kt, vt, *masks, *own)
    return o, lse


def _shift(block: int) -> int:
    """log2 of a block length (a power of two: it divides a sub-tile)."""
    return block.bit_length() - 1


# ---------------------------------------------------------------- backward

def _p_and_ds(q, k, v, do, lse, delta, mask, sel=None, rule=None,
              own=None):
    """Recomputed probabilities and score gradients of one strip, both
    f32 and both TRANSPOSED, ``(sk, sq)``: ``p^T = exp2(s^T - lse)``,
    ``ds^T = p^T * (dp^T - delta)``, from stats that are rows ``(1, sq)``.
    ``sel``: the strip of a data mask, turned round as the scores are;
    ``rule``: the block rule's (``_scores``); ``own``: the strip is a run of
    rows against their own positions' keys, in blocks of ``2 ** own``."""
    s = _scores(q, k, mask, transposed=True, rule=rule)
    if sel is not None:
        s = jnp.where(sel != 0, s, NEG_INF)
    if own is not None:
        s = _own_block(s, own)
    p = jnp.exp2(s - lse)
    dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _rows(ref, n, body):
    """``body(rows)`` over ``ref``'s rows, ``n`` at a time, in a loop that
    is not unrolled: a whole sequence's accumulator is too much
    straight-line code for one statement."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * n, n), n))
        return carry

    jax.lax.fori_loop(0, ref.shape[0] // n, step, None)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, delta_scr, *,
                dq_scale, causal, tiles, grid_qk, window=None, rep=1,
                sel_ref=None, bd=None):
    # Axis 2 walks the ``rep`` q heads of this KV head, each head's q tiles
    # in turn, axis 3 a q tile's kv tiles: dq gathers over axis 3 and
    # leaves once a q tile; dk and dv gather over BOTH, a whole sequence of
    # float32 in VMEM, and leave once a KV head.  ``bd`` as the forward's
    # (``flash_dkv_bd``): a head's q tiles are the noised stream's, then the
    # clean one's; the clean keys' dk and dv, the accumulators' second half,
    # gather over both streams' queries.
    t, ki = pl.program_id(2), pl.program_id(3)
    last_k = ki == pl.num_programs(3) - 1
    q_tiles = grid_qk[0] * (1 if bd is None else 2)
    qi, noised, rule = _streams(bd, t if rep == 1 else t % q_tiles,
                                grid_qk[0])
    block_q, block_k, sub_q, sub_k = tiles

    @pl.when((t == 0) & (ki == 0))
    def _init_kv():
        def zero(rows):
            dk_scr[rows] = jnp.zeros((block_k, dk_scr.shape[1]), jnp.float32)
            dv_scr[rows] = jnp.zeros((block_k, dv_scr.shape[1]), jnp.float32)

        _rows(dk_scr, block_k, zero)

    @pl.when(ki == 0)
    def _init_q():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # delta, the float a query row ``sum_d o[r, d] do[r, d]``, once a q
        # tile and as a ROW, which is how the transposed scores take it: the
        # float32 products turned round (as dq is on its way out) and summed
        # down the sublanes.  On the vector unit and in float32 alone: ``ds
        # = p (dp - delta)`` cancels, and a product on the MXU would round.
        for s0 in range(0, block_q, sub_q):
            qs = pl.ds(s0, sub_q)
            prod = o_ref[qs].astype(jnp.float32) * do_ref[qs].astype(
                jnp.float32)
            delta_scr[:, qs] = jnp.broadcast_to(
                jnp.sum(prod.T, axis=0, keepdims=True), (8, sub_q))

    def gather(qs, ks, keys, values, first_row, mask, own=None):
        """One strip's part of the three gradients: queries ``qs`` of the
        tile against the rows ``ks`` of the fetched ``keys`` and ``values``,
        which are the accumulators' rows from ``first_row()`` on."""
        q, do, k = q_ref[qs], do_ref[qs], keys[ks]
        # Transposed, (sk, sq): p^T and ds^T are what dv's and dk's
        # products take on the left, so no tile is turned round.
        p, ds = _p_and_ds(q, k, values[ks], do, lse_ref[:, qs],
                          delta_scr[:1, qs], mask,
                          None if sel_ref is None else sel_ref[ks, qs],
                          rule, own)
        # Grad matmuls in the INPUT dtype (bf16 on TPU): the MXU runs
        # bf16 natively; f32 operands would force multi-pass matmuls.
        ds = ds.astype(q.dtype)
        # this strip's rows of the whole-sequence accumulators
        rows = pl.ds(pl.multiple_of(first_row(), ks.size), ks.size)
        dv_scr[rows] += jnp.dot(p.astype(do.dtype), do,
                                preferred_element_type=jnp.float32)
        dk_scr[rows] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        # dq = ds k contracts the tile's FIRST dimension.  It gathers
        # turned round, dq^T = k^T ds^T (d, sq): the operand Mosaic turns is
        # the strip of k, not the tile, and the product's lanes are the q
        # rows, whole lane blocks whatever d (at d = 192 and 64 the plain
        # form pays for 256 and 128: 5 and 9 % of a call; PERF.md §6, PR 69).
        dq_scr[:, qs] += jax.lax.dot_general(
            k, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def update(qs, ks, mask):
        if ks.size > sub_k:
            # An interior tile comes whole.  Its strips run in a loop that
            # is NOT unrolled: as one call the tile's five products are
            # straight-line code, and past a size that code runs at half
            # speed (a dk/dv kernel's four at q/k head 192: 21.1 ms a call
            # for 10.4; PERF.md §6, PR 35).
            def strip(i, carry):
                update(qs, pl.ds(pl.multiple_of(ks.start + i * sub_k, sub_k),
                                 sub_k), mask)
                return carry

            return jax.lax.fori_loop(0, ks.size // sub_k, strip, None)
        if bd is None:
            first_row = lambda: ki * block_k + ks.start     # noqa: E731
        else:   # the clean keys: the accumulators' second half
            first_row = lambda: (    # noqa: E731
                ki * block_k + ks.start + grid_qk[1] * block_k)
        gather(qs, ks, k_ref, v_ref, first_row, mask)

    if bd is not None:
        @pl.when((ki == 0) & noised)
        def _own_blocks():
            # the noised keys at this q tile's own positions: the
            # accumulators' first half
            for s0 in range(0, block_q, sub_q):
                qs = pl.ds(s0, sub_q)
                gather(qs, qs, bd[1], bd[2], lambda: qi * block_q + s0,
                       None, own=bd[0])

    _walk_tile(causal, _tile_offset(causal, qi, ki, tiles, grid_qk), tiles,
               update, strips="k", window=window)

    @pl.when(last_k)
    def _finalize_q():
        # dL/dq as it came in = dq_scale * ds @ k, applied once here.
        dq_ref[...] = (dq_scr[...].T * dq_scale).astype(dq_ref.dtype)

    @pl.when((t == pl.num_programs(2) - 1) & last_k)
    def _finalize_kv():
        def leave(rows):
            # q arrives pre-scaled by c = sm_scale*log2e; the true gradient
            # is sm_scale * ds^T @ q_unscaled = ln2 * ds^T @ (q*c).
            dk_ref[rows] = (dk_scr[rows] * _LN2).astype(dk_ref.dtype)
            dv_ref[rows] = dv_scr[rows].astype(dv_ref.dtype)

        _rows(dk_scr, block_k, leave)


def _check_resident(sk, d, dv, dtype):
    """Refuse a backward pass whose KV head's dk and dv (float32 scratch
    and the block they leave in, rows padded to whole lane blocks) do not
    fit the kernel's VMEM: at 128-wide bf16 heads past 43690 keys."""
    lanes = sum(-(-width // _LANES) * _LANES for width in (d, dv))
    resident = sk * lanes * (4 + jnp.dtype(dtype).itemsize)
    if resident > _RESIDENT_BYTES:
        raise ValueError(
            f"flash attention's backward keeps a KV head's whole dk and dv "
            f"in VMEM: {sk} keys x ({d} + {dv}) take {resident} B of the "
            f"{_RESIDENT_BYTES} B it may (_RESIDENT_BYTES); split the "
            f"sequence over devices (ops.ring_attention) or call it a "
            f"segment of keys at a time")


def _bwd_call(qt, kt, vt, ot, lse, dot, dq_scale, causal, tiles, interpret,
              window=None, heads=None, sel_t=None, block=None):
    """qt, kt, vt, ot, dot in either addressing (``_grid_and_specs``); lse
    (b, h, sq), a float a row.  Returns (dqt, dkt, dvt), each addressed as
    its operand: dkt and dvt at k's and v's OWN head count, summed over
    each KV head's group of q heads inside the kernel.  ``sel_t (b, sk,
    sq)``: the forward's data mask turned round (``flash_dkv_dsa``);
    ``block``: two streams under the block rule (``flash_dkv_bd``).

    ``ot`` rides ``dot``'s spec: a block's index does not depend on the kv
    axis, so it is fetched once a q tile — ``block_q x dv`` of o's type,
    0.5 MB at 2048 x 128 bfloat16, twice buffered — and ``delta``'s row is
    ``(8, block_q)`` float32 of scratch, 64 KB: 1.06 MB of the ``_VMEM_LIMIT``
    100 MiB beside the 64 MiB ``_check_resident`` leaves dk and dv, where
    the ``(1, block_q)`` block of an XLA-made ``delta`` took 0.13."""
    b, h, h_kv, sq, sk, d, dv = _dims(qt, kt, vt, heads)
    _check_resident(sk, d, dv, kt.dtype)
    block_q = tiles[0]
    (nq, nk), specs = _grid_and_specs(qt, kt, vt, causal, tiles, window,
                                      heads, block is not None)
    q_t, o_t, k_t, v_t, k_all, v_all, stat_t = (specs[n] for n in (
        "q_t", "o_t", "k_t", "v_t", "k_all", "v_all", "stat_t"))
    rep = h // h_kv
    kernel, masks, own = _bwd_kernel, (), ()
    if sel_t is not None:
        kernel, masks = _with_sel(_bwd_kernel, 6), (sel_t,)
    if block is not None:
        kernel, own = _with_own(_bwd_kernel, 6, _shift(block)), (kt, vt)
    return pl.pallas_call(
        functools.partial(kernel, dq_scale=dq_scale, causal=causal,
                          tiles=tiles, grid_qk=(nq, nk), window=window,
                          rep=rep),
        grid=(b, h_kv, rep * (sq // block_q), nk),
        in_specs=[q_t, k_t, v_t, o_t, o_t, stat_t,
                  *(specs["sel_t"] for _ in masks),
                  *(specs[n] for n in ("kn_t", "vn_t")[:len(own)])],
        out_specs=[q_t, k_all, v_all],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (qt, kt, vt)],
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32),
                        pltpu.VMEM((sk, d), jnp.float32),
                        pltpu.VMEM((sk, dv), jnp.float32),
                        pltpu.VMEM((8, block_q), jnp.float32)],
        compiler_params=_compiler_params(interpret, sequential=2),
        interpret=interpret,
        name=_kernel_name("flash_dkv", window, sel_t, block),
    )(qt, kt, vt, ot, dot, lse[:, :, None], *masks, *own)


# ----------------------------------------------------------------- public

def _in_place(q, v) -> bool:
    """Whether a head fills whole lane blocks, so the kernels can cut it out
    of the model's ``(b, s, heads x d)`` by the index map alone."""
    return q.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0


def _enter(x, in_place: bool):
    """The model's ``(b, s, heads, d)`` as the kernels address it: ``(b, s,
    heads x d)`` where it stands (the reshape moves nothing), else turned
    round to ``(b, heads, s, d)``."""
    if in_place:
        return x.reshape(*x.shape[:2], -1)
    return jnp.transpose(x, (0, 2, 1, 3))


def _leave(x, heads: int):
    """``_enter``'s inverse, for what a kernel wrote: three dimensions are
    an array left in place, four one turned round."""
    if x.ndim == 3:
        return x.reshape(*x.shape[:2], heads, -1)
    return jnp.transpose(x, (0, 2, 1, 3))


def q_prescale(sm_scale: float, dtype) -> float:
    """What the kernels take q multiplied by (the softmax runs in the log2
    domain), as the number of q's type the multiply sees."""
    return float(np.asarray(sm_scale * _LOG2E, jnp.dtype(dtype)))


def _forward(q, k, v, sm_scale, causal, tiles, interpret, window,
             prescaled=False, block=None):
    """``flash_fwd`` on the model's q, k, v: (q, k, v as the kernels took
    them — the residuals of the backward pass —, o and lse as the kernel
    wrote them)."""
    in_place = _in_place(q, v)
    heads = (q.shape[2], k.shape[2]) if in_place else None
    # The pre-scale is XLA's: it rides in the fusion that writes q (a norm's,
    # the 4-D RoPE's).  A custom call that writes q has no fusion for it to
    # ride in — the multiply would be a pass of its own over q —, so the
    # RoPE kernel applies it on its way out and hands q in ``prescaled``.
    qs = q if prescaled else (q * (sm_scale * _LOG2E)).astype(q.dtype)
    qt, kt, vt = (_enter(x, in_place) for x in (qs, k, v))
    ot, lse = _fwd_call(qt, kt, vt, causal, tiles, interpret, window, heads,
                        block=block)
    return (qt, kt, vt), ot, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, sm_scale, causal, tiles, interpret, window=None,
           prescaled=False, block=None):
    """``tiles`` = (block_q, block_k, sub_q, sub_k): each sub divides its
    block, each block its sequence (under ``block``, the block rule, ONE
    stream's of q, k, v ``[noised ; clean]``).  k and v come with their OWN
    head count, a divisor of q's.  ``prescaled``: q is already times
    ``q_prescale`` (and its gradient is the gradient to THAT q)."""
    _, ot, _ = _forward(q, k, v, sm_scale, causal, tiles, interpret, window,
                        prescaled, block)
    return _leave(ot, q.shape[2])


# The residuals a layer checkpoint keeps (``models/llama.py`` hands these
# names to its policy): with the kernel's output and log-sum-exp held, the
# backward pass needs no second ``flash_fwd``.  q, k, v stay recomputed.
SAVED_RESIDUALS = ("flash_out", "flash_lse")


def _flash_fwd(q, k, v, sm_scale, causal, tiles, interpret, window=None,
               prescaled=False, block=None):
    operands, ot, lse = _forward(q, k, v, sm_scale, causal, tiles, interpret,
                                 window, prescaled, block)
    ot = checkpoint_name(ot, "flash_out")
    # One lane of the 128 the kernel writes: a float a row is what is
    # worth holding, and what the backward kernel takes (as rows).
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return _leave(ot, q.shape[2]), (*operands, ot, lse)


def _flash_bwd(sm_scale, causal, tiles, interpret, window, prescaled, block,
               res, do):
    qt, kt, vt, ot, lse = res
    in_place = qt.ndim == 3     # the residuals' own shapes say how they stand
    h = do.shape[2]
    h_kv = kt.shape[2] * h // qt.shape[2] if in_place else kt.shape[1]
    # dq leaves as ``ds @ k`` times this: d scores / d q as it came in
    dq_scale = (sm_scale / q_prescale(sm_scale, qt.dtype) if prescaled
                else sm_scale)
    dqt, dkt, dvt = _bwd_call(qt, kt, vt, ot, lse, _enter(do, in_place),
                              dq_scale, causal, tiles, interpret, window,
                              (h, h_kv) if in_place else None, block=block)
    return _leave(dqt, h), _leave(dkt, h_kv), _leave(dvt, h_kv)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = MAX_BLOCK, block_k: int = MAX_BLOCK,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None,
                    q_prescaled: bool = False,
                    block: Optional[int] = None) -> jax.Array:
    """Memory-efficient MHA.  q: (b, sq, h, d); k: (b, sk, h_kv, d); v:
    (b, sk, h_kv, dv), the output (b, sq, h, dv): v's head size may differ
    from q's and k's (a latent-attention mixer's 192 / 128).

    ``q_prescaled``: q comes times ``q_prescale(sm_scale, q.dtype)`` — from
    a kernel that wrote it and applied the kernels' pre-scale on its way out
    (``ops/rotary.py``), where XLA's own multiply would be a pass over q —
    and the gradient returned is the gradient to q as it came.

    ``window`` (causal only): query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``; the kernels then neither fetch nor compute a tile beyond
    either edge (``flash_*_win``).  A window that reaches every key the
    diagonal leaves (``window >= sk``) cuts nothing: the plain kernels run.

    ``block`` (causal, no window): the BLOCK-DIFFUSION rule, a third edge.
    The rows of q, k and v are TWO STREAMS of one sequence of ``sq / 2``
    positions, ``[noised ; clean]``, and a row sees a column by the blocks
    of ``block`` positions the two lie in and the streams they come from
    (``block_mask`` has the four cases).  The kernels (``flash_*_bd``) run
    each stream's q tiles over the clean keys up to the tile's diagonal —
    whole sub-tiles below it, the test on ``row // block`` and ``column //
    block`` on it, the clean stream's ``<=``, the noised one's ``<`` — and
    the noised stream's besides over its OWN keys, the diagonal squares
    alone: nothing runs for the clean rows against noised keys or for two
    different blocks of noised ones, and no mask array exists.  For a
    ``block`` that divides the compute sub-tile (a power of two); else the
    XLA reference, the dense mask.

    Grouped-query attention: k and v may have fewer heads than q, ``h %
    h_kv == 0``.  Nothing repeats them: the kernels find a q head's KV
    head by the index map, and ``dk`` / ``dv`` come back at ``h_kv`` heads,
    summed over each group in the kernel (``_grid_and_specs``).
    ``block_q``/``block_k`` are upper bounds of the fetch tile; the tile
    and the compute sub-tile follow the call's shapes (``choose_tiles``).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv heads "
                         f"{k.shape[2]}")
    window = live_window(window, k.shape[1], causal)
    if block is not None:
        _check_block(block, causal, window, q.shape[1], k.shape[1])
        tiles = block_tiles(q.shape[1] // 2, block,
                            max(q.shape[-1], v.shape[-1]), q.dtype, block_q,
                            block_k)
    else:
        tiles = choose_tiles(q.shape[1], k.shape[1], causal,
                             max(q.shape[-1], v.shape[-1]), q.dtype, block_q,
                             block_k, window)
    if tiles is None:
        # No block >= 8 tiles the sequence exactly: the XLA reference is
        # correct, at O(S^2) memory.
        from ray_tpu.ops.layers import repeat_kv_heads
        k, v = repeat_kv_heads(q, k, v)
        if q_prescaled:
            sm_scale = sm_scale / q_prescale(sm_scale, q.dtype)
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window, block=block)
    return _flash(q, k, v, sm_scale, causal, tiles, interpret, window,
                  q_prescaled, block)


def live_window(window: Optional[int], sk: int, causal: bool = True
                ) -> Optional[int]:
    """``window`` where it cuts any of ``sk`` keys off, else None."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"window {window}: a whole number from 1, the far "
                         "edge of the causal mask")
    return window if window < sk else None


def _fit_block(block: int, seq: int) -> Optional[int]:
    """Largest ``block / 2**n`` >= 8 that divides ``seq``: the kernels
    have no partial-block masking, so blocks tile the sequence exactly."""
    block = min(block, seq)
    while block >= 8:
        if seq % block == 0:
            return block
        block //= 2
    return None


def choose_tiles(sq: int, sk: int, causal: bool, d: int, dtype,
                 block_q: int = MAX_BLOCK, block_k: int = MAX_BLOCK,
                 window: Optional[int] = None):
    """(block_q, block_k, sub_q, sub_k) for a call from what it can see,
    or None where no block tiles a sequence.  The fetch tile is the
    largest under the caps (the caller's, the mode's, and ``_BLOCK_BYTES``
    for one operand's block).  Without a mask every sub-tile is interior,
    so the sub-tile is the tile.  Under it the sub-tile is the widest of
    ``SUB_TILES`` that computes at most ``MAX_EXECUTED`` times the causal
    pairs (``causal_tile_counts``; under a ``window`` the pairs it leaves),
    else the narrowest: wide strips feed the MXU longer products, narrow
    ones compute less beyond the edges."""
    rows = _BLOCK_BYTES // (d * jnp.dtype(dtype).itemsize)
    caps = (MAX_BLOCK, MAX_BLOCK) if causal else UNMASKED_BLOCK
    block_q = _fit_block(min(block_q, caps[0], rows), sq)
    block_k = _fit_block(min(block_k, caps[1], rows), sk)
    if block_q is None or block_k is None:
        return None
    if not causal:
        return block_q, block_k, block_q, block_k

    for sub in SUB_TILES:
        tiles = (block_q, block_k,
                 _fit_block(sub, block_q), _fit_block(sub, block_k))
        n = causal_tile_counts(sq, sk, *tiles, window=window)
        if n["executed_pairs"] <= MAX_EXECUTED * n["causal_pairs"]:
            break
    return tiles


# ------------------------------------------------------------ the block rule

def block_tiles(length: int, block: int, d: int, dtype,
                block_q: int = MAX_BLOCK, block_k: int = MAX_BLOCK):
    """``choose_tiles`` for ONE stream of ``length`` positions under the
    block rule (the streams tile alike; off the diagonal a sub-tile is whole
    or dead exactly as under the causal edge, so the causal choice is the
    choice), or None where ``block`` does not divide the compute sub-tile:
    the test on the diagonal counts rows and columns in whole blocks from a
    sub-tile's corner."""
    tiles = choose_tiles(length, length, True, d, dtype, block_q, block_k)
    if tiles is None or block < 2 or block & (block - 1) or any(
            t % block for t in tiles[2:]):
        return None
    return tiles


def block_needed_pairs(length: int, block: int) -> int:
    """(q, k) pairs the block rule asks for, a head: row ``r`` of either
    stream reads ``(r // block + 1) block`` keys — the clean one its own
    block and the earlier ones, the noised one the earlier clean blocks and
    its own noised block — so ``length (length + block)`` together."""
    return length * (length + block)


def block_tile_counts(length: int, tiles) -> dict:
    """What the ``flash_*_bd`` schedule executes for one (batch, head):
    both streams' live sub-tiles on the clean keys (``causal_tile_counts``
    of one stream, twice) and the noised stream's diagonal squares
    (``sub_q`` rows on their own ``sub_q`` keys)."""
    n = causal_tile_counts(length, length, *tiles)
    sub_q = min(tiles[2], tiles[0])
    return {"executed_pairs": 2 * n["executed_pairs"] + length * sub_q,
            "diagonal": 2 * n["diagonal"] + length // sub_q,
            "interior": 2 * n["interior"]}


def block_schedule_off(length: int, block: int, tiles) -> jax.Array:
    """The pairs on which the kernels' schedule and ``block_mask`` DISAGREE,
    over one strip of ``sub_q`` rows of each stream (the first strip of the
    middle q tile: it has whole tiles before it, a diagonal and dead tiles
    after): the strip's row of kv tiles walked by ``_walk_tile`` and tested
    by ``_scores`` as the forward kernel does it, on scores of 0, then the
    own-block square; an int32 scalar, 0 for a sound schedule.  A few
    ``(sub_q, block_k)`` integer tiles of constants: it costs a step nothing
    to speak of."""
    block_q, block_k, sub_q, _ = tiles
    shift, qi = _shift(block), (length // block_q) // 2
    zeros = lambda n: jnp.zeros((n, 1), jnp.float32)   # noqa: E731
    first, off = qi * block_q, 0
    for strict in (1, 0):                    # the noised stream, the clean
        clean = jnp.zeros((sub_q, length), bool)

        def body(ki, qs, ks, mask):
            nonlocal clean
            if qs.start == 0:       # the strip: what the kernel's test sees
                at = ki * block_k + ks.start
                clean = clean.at[:, at:at + ks.size].set(_scores(
                    zeros(qs.size), zeros(ks.size), mask,
                    rule=(shift, strict)) > NEG_INF / 2)

        for ki in range(length // block_k):
            _walk_tile(True, ki * block_k - first, tiles,
                       functools.partial(body, ki), "q")
        noised = jnp.zeros_like(clean)
        if strict:
            noised = noised.at[:, first:first + sub_q].set(_own_block(
                jnp.zeros((sub_q, sub_q), jnp.float32), shift) > NEG_INF / 2)
        want = block_mask(length, block, (0 if strict else length) + first,
                          sub_q)
        off = off + jnp.sum(jnp.concatenate([noised, clean], 1) != want)
    return off.astype(jnp.int32)
