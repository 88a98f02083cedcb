"""Learned sparse attention (the lightning indexer and top-k selection of
arXiv:2512.02556 §2.1, on a grouped-query model): attention whose mask is
DATA.  A small INDEXER scores every causal (query, key) pair,

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        s <= t, float32

(``H`` index heads against ONE key head; ``w`` comes with its scalings
applied), a query reads the ``topk`` keys of highest score and no other,
and the indexer is trained towards the attention it serves by its own loss,
``KL(p_t || softmax over S_t of I[t, .])``, ``p_t`` the heads' attention
probabilities over the selection ``S_t``, summed and divided by their
count, detached.

What is here, in the order a layer runs it (``models/blocks/attention.py``
opens the scopes):

- ``index_scores`` (scope ``dsa_index``): the ``(b, s, s)`` float32 matrix,
  ``NEG_INF`` above the diagonal, ``q_chunk`` queries at a time so that
  the ``H`` heads' products never stand whole; its gradient (``custom_vjp``;
  ``index_grads``, float32, where a layer asks for it in the forward pass)
  remakes a chunk's products from q, k and w and keeps nothing of them.
- ``select`` (scope ``dsa_select``): a row's ``topk``-th highest score
  ``tau`` and, for the rule of TIES (the lower key wins), the highest key
  ``tie`` admitted AT ``tau``: ``lax.top_k`` breaks ties towards the lower
  index, so the selection is ``I > tau`` or ``I == tau and s <= tie`` —
  exactly ``topk`` keys a row past the first ``topk`` rows, every causal key
  before.  Two numbers a row are ALL the layer checkpoint keeps of a
  selection (``SAVED_RESIDUALS``, beside the loss's three unit gradients):
  the backward pass selects nothing again.  The kernel (``sparse_select``)
  counts a block of rows over the columns those rows can select from — up
  to the block's last row, in whole chunks, never fewer than ``topk`` — and
  over no other: the rest of the square is ``NEG_INF`` by construction.  It
  walks the tie's key by its bits only in a block where a tie BINDS (a row
  with more keys at ``tau`` than fit); elsewhere ``tie`` is the last key at
  ``tau``, one pass.  The share of blocks that walked is ``select``'s third
  output, the step statistic ``dsa_tie_walk_share``.
- ``masks`` (scope ``dsa_select`` too): everything else the layer takes
  from the scores, by ONE kernel pass over their live tiles
  (``sparse_mask``) — the mask, int8 ``(b, s, s)``; the mask with the keys
  first, what the flash backward reads; a row's log-sum-exp over its
  selected scores, what the KL's kernel takes; a row's count of selected
  keys.  It runs in both phases, after ``select`` in the forward pass and
  from the checkpoint's ``tau`` and ``tie`` in the rematerialised one.  A
  tile past the diagonal is read by nobody and written as ZEROS, so either
  mask read whole is ``selection``'s: the XLA form, the tests' oracle and
  what runs where no kernel does (``attend`` then turns the mask round
  itself, ``kl_and_gradient`` makes its own log-sum-exp).
- ``attend`` (scope ``attention``): softmax attention over the selected
  pairs alone: the flash kernels of ``ops/attention.py`` under the mask
  (``flash_fwd_dsa`` / ``flash_dkv_dsa``: the causal tile walk as it is,
  every computed pair tested against the mask's tile; DENSE products, of
  which the selection needs ``selected / causal``), or in XLA where no
  kernel runs (``attn_impl`` other than ``flash``, a sequence no block
  tiles).  Returns the output and each row's log-sum-exp, base 2.
- ``kl_and_gradient`` (scope ``dsa_loss``): the heads' probabilities remade
  from q, k and the log-sum-exp, ``q_chunk`` queries at a time, their mean,
  the KL a row AND its gradient, which reaches the index scores alone:
  ``softmax_S(I) - p`` on the selection, ``(b, s, s)`` float32.  Both are
  values of the forward pass.  ``index_grads`` (scope ``dsa_index``) turns
  that gradient, there and then, into what the step wants — the UNIT
  gradients to q_idx, k_idx and w, float32 — and the layer ties them to the
  loss, ONE number a sequence (``models/blocks/attention.py::
  _indexer_loss``, whose forward rule calls the two): they are all the loss
  hands its backward pass and all the checkpoint keeps of it, the backward
  rule is the cotangent (a scalar a sequence, so exact for k_idx too) times
  each, and neither kernel runs a second time under the checkpoint.
  ``indexer_kl`` is the plain row form under autodiff, the tests' oracle.

Nothing outside ``S_t`` is read by the softmax or any gradient.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.attention import NEG_INF, _LANES, _LN2, _LOG2E

# What the layer checkpoint keeps of a selection, two numbers a row, and of
# the indexer's loss, its UNIT gradients to q_idx, k_idx and w, float32
# (named by the layer; 72 MB a layer at 16384 tokens of 16 x 64, where the
# KL's ``(s, s)`` gradient they are made from is 1.07 GB).
UNIT_GRADIENTS = ("dsa_dq_idx", "dsa_dk_idx", "dsa_dw")
SAVED_RESIDUALS = ("dsa_tau", "dsa_tie", *UNIT_GRADIENTS)
# A chunk's products, all heads, may take this much (float32 bytes).
_CHUNK_BYTES = 256 * 1024 * 1024


def _chunk(s: int, row_bytes: int) -> int:
    """Queries a chunk: the largest power-of-two share of ``s`` whose
    products (``row_bytes`` a query) stay under ``_CHUNK_BYTES``."""
    c = s
    while c > 8 and c % 2 == 0 and c * row_bytes > _CHUNK_BYTES:
        c //= 2
    return c


def _split(x, c: int):
    """``(b, s, ...)`` as ``(s / c, b, c, ...)``: chunks of axis 1 first."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // c, c, *x.shape[2:]), 1, 0)


def _join(y):
    """``_split``'s inverse."""
    n, b, c = y.shape[:3]
    return jnp.moveaxis(y, 0, 1).reshape(b, n * c, *y.shape[3:])


def _by_chunks(fn, c: int, *rows):
    """``fn(first row, *chunks)`` over the operands' axis 1 in chunks of
    ``c``, the results laid back along axis 1."""
    s = rows[0].shape[1]
    if s == c:
        return fn(0, *rows)
    out = jax.lax.map(lambda a: fn(a[0], *a[1:]),
                      (jnp.arange(0, s, c), *(_split(x, c) for x in rows)))
    return jax.tree.map(_join, out)


def _causal(first, c: int, s: int):
    """``(c, s)``: key ``j`` at or before query ``first + i``."""
    return (first + jnp.arange(c))[:, None] >= jnp.arange(s)[None, :]


# ------------------------------------------------------------ index scores

def _head_products(q, k):
    """``(b, c, H, s)`` float32: every index head of a chunk against the
    one key head."""
    return jnp.einsum("bchd,bsd->bchs", q, k,
                      preferred_element_type=jnp.float32)


def _index_scores_xla(q_idx, k_idx, w):
    b, s, heads, _ = q_idx.shape

    def one(first, q, w):
        # ``+ 0.0``: a score of -0.0 (every head's ReLU shut, the weights
        # negative) is 0.0, so that no order tells the two apart
        scores = jnp.sum(jax.nn.relu(_head_products(q, k_idx))
                         * w[..., None], axis=2) + 0.0
        return jnp.where(_causal(first, q.shape[1], s), scores, NEG_INF)

    return _by_chunks(one, _chunk(s, 4 * b * heads * s), q_idx, w)


def _index_grads_xla(q_idx, k_idx, w, g):
    """A chunk's products are made again; dk gathers over the chunks.  All
    three float32."""
    b, s, heads, d = q_idx.shape
    c = _chunk(s, 4 * b * heads * s)

    def one(dk, chunk):
        q, w_, g_ = chunk
        prod = _head_products(q, k_idx)
        dw = jnp.einsum("bchs,bcs->bch", jax.nn.relu(prod), g_)
        ds = jnp.where(prod > 0, g_[:, :, None, :] * w_[..., None], 0.0
                       ).astype(q.dtype)
        dq = jnp.einsum("bchs,bsd->bchd", ds, k_idx,
                        preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("bchs,bchd->bsd", ds, q,
                             preferred_element_type=jnp.float32)
        return dk, (dq, dw)

    dk, (dq, dw) = jax.lax.scan(
        one, jnp.zeros((b, s, d), jnp.float32),
        tuple(_split(x, c) for x in (q_idx, w, g)))
    return _join(dq), dk, _join(dw)


# The same as kernels: a grid step holds a (queries, keys) tile and walks
# the index heads over it, so a head's products live in VMEM alone.  The
# queries come head-first, ``(b, H, s, di)``: a head is then a whole block.
INDEX_TILE = (512, 512)


def _tile(s: int, tile):
    """(queries, keys) a grid step of ``tile`` holds of a sequence of
    ``s``."""
    return tuple(min(t, s) for t in tile)


def _fits(s: int, tile) -> bool:
    """Whether whole lane tiles of ``tile`` divide the sequence."""
    return all(s % t == 0 and t % _LANES == 0 for t in _tile(s, tile))


def _interpret(interpret: Optional[bool]) -> bool:
    return attention._interpret_default() if interpret is None else interpret


def _live_k(bq: int, bk: int):
    """``(i, j) ->`` key tile ``j``, or the last one query tile ``i`` sees:
    a dead grid step names its live neighbour's block and copies nothing."""
    return lambda i, j: jnp.minimum(j, attention._last_live_k(i, bq, bk))


def _scores_kernel(q_ref, k_ref, w_ref, out_ref, *, heads, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = tile
    off = ki * bk - qi * bq     # first column minus first row

    @pl.when(off < bq)
    def _tile():
        k = k_ref[...]
        # gathered onto +0.0, a score is never -0.0 (``_index_scores_xla``)
        acc = jnp.zeros((bq, bk), jnp.float32)
        for j in range(heads):
            prod = jax.lax.dot_general(
                q_ref[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(prod, 0.0) * w_ref[:, j:j + 1]
        seen = (jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)) >= off
        out_ref[...] = jnp.where(seen, acc, NEG_INF)

    @pl.when(off >= bq)
    def _dead():
        out_ref[...] = jnp.full_like(out_ref, NEG_INF)


def _index_specs(s, heads, d):
    """The tile and the specs of q, k, w, a tile of ``(s, s)`` at the grid
    step's place and one at its live neighbour's."""
    bq, bk = _tile(s, INDEX_TILE)
    live = _live_k(bq, bk)
    q = pl.BlockSpec((None, heads, bq, d), lambda b_, i, j: (b_, 0, i, 0))
    k = pl.BlockSpec((None, bk, d), lambda b_, i, j: (b_, live(i, j), 0))
    w = pl.BlockSpec((None, bq, heads), lambda b_, i, j: (b_, i, 0))
    here = pl.BlockSpec((None, bq, bk), lambda b_, i, j: (b_, i, j))
    seen = pl.BlockSpec((None, bq, bk), lambda b_, i, j: (b_, i, live(i, j)))
    return (bq, bk), q, k, w, here, seen


def _params(interpret, semantics):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=attention._VMEM_LIMIT)


def _scores_call(q_idx, k_idx, w, interpret):
    b, s, heads, d = q_idx.shape
    (bq, bk), q, k, w_, here, _ = _index_specs(s, heads, d)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, tile=(bq, bk)),
        grid=(b, s // bq, s // bk), in_specs=[q, k, w_], out_specs=here,
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "parallel")),
        interpret=interpret, name="sparse_scores",
    )(jnp.moveaxis(q_idx, 2, 1), k_idx, w)


def _grads_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref,
                  dq_scr, dk_scr, dw_scr, *, heads, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    bq, bk = tile

    @pl.when((qi == 0) & (ki == 0))
    def _init_k():
        dk_scr[...] = jnp.zeros_like(dk_scr)

    @pl.when(ki == 0)
    def _init_q():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(ki * bk - qi * bq < bq)
    def _tile():
        k, g = k_ref[...], g_ref[...]
        rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        dk = jnp.zeros(k.shape, jnp.float32)
        dw = jnp.zeros((bq, _LANES), jnp.float32)
        for j in range(heads):
            q = q_ref[j]
            prod = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw = dw + jnp.sum(jnp.maximum(prod, 0.0) * g, axis=-1,
                              keepdims=True) * (lane == j)
            ds = jnp.where(prod > 0.0, g * w_ref[:, j:j + 1], 0.0
                           ).astype(q.dtype)
            dq_scr[j] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_scr[rows] += dk
        dw_scr[...] += dw

    @pl.when(ki == nk - 1)
    def _leave_q():
        dq_ref[...] = dq_scr[...]
        dw_ref[...] = dw_scr[...]

    @pl.when((qi == nq - 1) & (ki == nk - 1))
    def _leave_k():
        dk_ref[...] = dk_scr[...]


def _grads_call(q_idx, k_idx, w, g, interpret):
    b, s, heads, d = q_idx.shape
    (bq, bk), q, k, w_, _, g_at = _index_specs(s, heads, d)
    whole = pl.BlockSpec((None, s, d), lambda b_, i, j: (b_, 0, 0))
    lanes = pl.BlockSpec((None, bq, _LANES), lambda b_, i, j: (b_, i, 0))
    # the barrier: where the three are a layer's residuals, XLA would have
    # the kernel write ``dk`` straight into the layer scan's stack, in a
    # fusion whose scoped VMEM is XLA's 16 MB and not ``_params``' limit
    # (the kernel holds 34 MB at 16384 tokens: the compile fails)
    dq, dk, dw = jax.lax.optimization_barrier(pl.pallas_call(
        functools.partial(_grads_kernel, heads=heads, tile=(bq, bk)),
        grid=(b, s // bq, s // bk), in_specs=[q, k, w_, g_at],
        out_specs=[q, whole, lanes],
        out_shape=[jax.ShapeDtypeStruct((b, heads, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, bq, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=_params(interpret,
                                ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="sparse_scores_bwd",
    )(jnp.moveaxis(q_idx, 2, 1), k_idx, w, g))
    return jnp.moveaxis(dq, 1, 2), dk, dw[..., :heads]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _index_scores(q_idx, k_idx, w, kernel):
    if kernel is None:
        return _index_scores_xla(q_idx, k_idx, w)
    return _scores_call(q_idx, k_idx, w, kernel)


def _index_scores_fwd(q_idx, k_idx, w, kernel):
    return _index_scores(q_idx, k_idx, w, kernel), (q_idx, k_idx, w)


def _index_grads(q_idx, k_idx, w, g, kernel):
    """``g (b, s, s)``: what reaches the scores (0 wherever a pair is not
    selected).  The three gradients in float32."""
    if kernel is None:
        return _index_grads_xla(q_idx, k_idx, w, g)
    return _grads_call(q_idx, k_idx, w, g, kernel)


def _index_scores_bwd(kernel, res, g):
    return tuple(d.astype(x.dtype) for d, x in zip(
        _index_grads(*res, g, kernel), res))


_index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def _index_kernel(s: int, kernels: bool, interpret: Optional[bool]):
    """The index kernels' ``interpret``, or None where XLA runs."""
    return (_interpret(interpret)
            if kernels and _fits(s, INDEX_TILE) else None)


def index_scores(q_idx, k_idx, w, *, kernels: bool = True,
                 interpret: Optional[bool] = None):
    """``q_idx (b, s, H, d)``, ``k_idx (b, s, d)``, ``w (b, s, H)`` float32
    -> ``I (b, s, s)`` float32, ``NEG_INF`` where the key is after the
    query.  By the kernels ``sparse_scores`` / ``sparse_scores_bwd`` where
    ``kernels`` and the tiles divide the sequence, else in XLA."""
    return _index_scores(q_idx, k_idx, w.astype(jnp.float32), _index_kernel(
        q_idx.shape[1], kernels, interpret))


def index_grads(q_idx, k_idx, w, g, *, kernels: bool = True,
                interpret: Optional[bool] = None):
    """What ``g (b, s, s)`` reaching ``index_scores``' output sends to its
    three operands, ``(dq_idx, dk_idx, dw)`` in FLOAT32 whatever the
    operands' types (the kernel's accumulators as they stand), on detached
    operands: a value of the forward pass, by ``sparse_scores_bwd`` where
    ``index_scores`` is ``sparse_scores``."""
    q_idx, k_idx, w, g = jax.lax.stop_gradient(
        (q_idx, k_idx, w.astype(jnp.float32), g))
    return _index_grads(q_idx, k_idx, w, g, _index_kernel(
        q_idx.shape[1], kernels, interpret))


# --------------------------------------------------------------- selection

def _select_xla(scores, topk: int):
    s = scores.shape[-1]

    def one(first, rows):
        vals, keys = jax.lax.top_k(rows, topk)
        tau = vals[..., -1]
        tie = jnp.max(jnp.where(vals == tau[..., None], keys, -1), axis=-1)
        return tau, tie.astype(jnp.int32)

    # a row of a sort is its keys and their numbers
    return _by_chunks(one, _chunk(s, 8 * scores.shape[0] * s), scores)


# The same by a kernel that SORTS NOTHING: a block of rows stays in VMEM
# and the ``topk``-th highest value is found bit by bit — a float's bits,
# the sign folded in, order as whole numbers, and ``count(key >= v) >=
# topk`` says whether the next bit of ``v`` is set: 32 counts a row, exact.
# A count runs over the columns a row of the block CAN select from and no
# other: ``[0, W)``, ``W`` the block's last row + 1 rounded up to whole
# chunks of ``SELECT_CHUNK`` columns and never under ``topk`` (a row of
# fewer than ``topk`` causal keys counts its ``NEG_INF`` columns up to
# there, as ``lax.top_k`` takes them); every column past ``W`` is
# ``NEG_INF`` in every row of the block and is never read.  Then the rule of
# ties.  Where ``count(key >= v) == topk`` in every row of the block — all
# but one or two blocks in a hundred on float32 scores nobody rounded
# (``PERF.md`` §6, PR 73) — every key AT the value is admitted and the last
# of them is ONE pass (the
# highest column with ``key == v``).  Only a block with a row whose tie
# BINDS (more keys at the value than fit) walks: the ``c``-th key at the
# value from the left, ``c`` of them fitting, found by its bits, 15 counts
# more at 16384 columns.  The data decide, block by block; the kernel says
# which blocks walked in the spare lanes of ``tie`` (``select``'s third).
SELECT_ROWS = 32
SELECT_CHUNK = 2048


def _select_chunk(s: int) -> int:
    """Columns a trip of a count's loop: the largest halving of
    ``SELECT_CHUNK`` that divides ``s`` (a multiple of the lane width)."""
    c = SELECT_CHUNK
    while s % c:
        c //= 2
    return c


def _ordered(x):
    """float32 -> int32 whose order as whole numbers is the floats'."""
    bits = pltpu.bitcast(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_kernel(x_ref, key_ref, tie_ref, key_scr, *, topk, chunk):
    rows, s = x_ref.shape
    last = (pl.program_id(1) + 1) * rows        # the block's last row + 1
    trips = jnp.maximum(pl.cdiv(last, chunk), pl.cdiv(topk, chunk))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

    def order(c, _):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        key_scr[:, at] = _ordered(x_ref[:, at])

    jax.lax.fori_loop(0, trips, order, None)

    def sweep(step, acc):
        """``acc = step(acc, keys, columns)`` over the counted columns, a
        lane block ``(rows, _LANES)`` at a time, left to right."""
        def trip(c, acc):
            def block(j, acc):
                first = pl.multiple_of(c * chunk + j * _LANES, _LANES)
                return step(acc, key_scr[:, pl.ds(first, _LANES)],
                            first + lane)

            # straight-line code, traced once
            return jax.lax.fori_loop(0, chunk // _LANES, block, acc,
                                     unroll=True)

        return jax.lax.fori_loop(0, trips, trip, acc)

    def count(hit):
        """``(rows, 1)`` float32: the counted columns ``hit(keys, columns)``
        marks.  Gathered as whole numbers a lane block, reduced across the
        lanes ONCE, as floats (under 2**24: exact; the whole-number reduce
        reads 2 % slower a call, ``PERF.md`` §6, PR 73)."""
        acc = sweep(lambda acc, k, col: acc + hit(k, col).astype(jnp.int32),
                    jnp.zeros((rows, _LANES), jnp.int32))
        return jnp.sum(acc.astype(jnp.float32), axis=-1, keepdims=True)

    def value_bit(i, carry):
        v, n_ge = carry     # n_ge = count(key >= v), lane-replicated as v
        cand = jnp.where(i == 0, jnp.zeros_like(v),
                         v | jnp.left_shift(jnp.int32(1), 31 - i))
        n = count(lambda k, _: k >= cand)
        take = n >= topk
        return jnp.where(take, cand, v), jnp.where(take, n, n_ge)

    v, n_ge = jax.lax.fori_loop(0, 32, value_bit, (
        jnp.full((rows, _LANES), -2 ** 31, jnp.int32),
        jnp.full((rows, 1), (trips * chunk).astype(jnp.float32))))
    key_ref[...] = v
    binds = jnp.sum(jnp.abs(n_ge - topk)) > 0.0     # in some row of the block

    @pl.when(jnp.logical_not(binds))
    def _every_key_at_v():
        m = sweep(lambda acc, k, col: jnp.maximum(
            acc, jnp.where(k == v, col, -1)),
            jnp.full((rows, _LANES), -1, jnp.int32))
        m = jnp.max(m.astype(jnp.float32), axis=-1, keepdims=True)
        tie_ref[...] = jnp.where(lane == 0, m.astype(jnp.int32), 0)

    @pl.when(binds)
    def _walk():
        fit = topk - count(lambda k, _: k > v)  # of the keys at v, taken

        def key_bit(i, m):
            cand = m | jnp.left_shift(
                jnp.int32(1), (s - 1).bit_length() - 1 - i)
            below = count(lambda k, col: (k == v) & (col < cand))
            return jnp.where(below < fit, cand, m)

        m = jax.lax.fori_loop(0, (s - 1).bit_length(), key_bit,
                              jnp.zeros((rows, _LANES), jnp.int32))
        tie_ref[...] = jnp.where(lane == 0, m, 1)


def _select_call(scores, topk: int, interpret):
    """``(tau, tie, the share of a sequence's row blocks that walked the
    tie (b,))``."""
    b, s, _ = scores.shape
    rows = min(SELECT_ROWS, s)
    lanes = pl.BlockSpec((None, rows, _LANES), lambda b_, i: (b_, i, 0))
    key, tie = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=_select_chunk(s)),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((None, rows, s), lambda b_, i: (b_, i, 0))],
        out_specs=[lanes, lanes],
        out_shape=[jax.ShapeDtypeStruct((b, s, _LANES), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=_params(interpret, ("parallel", "parallel")),
        interpret=interpret, name="sparse_select",
    )(scores)
    key = key[..., 0]
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key)
    walked = jnp.mean(tie[:, ::rows, 1].astype(jnp.float32), axis=1)
    return (jax.lax.bitcast_convert_type(bits, jnp.float32), tie[..., 0],
            walked)


def select(scores, topk: int, *, kernels: bool = True,
           interpret: Optional[bool] = None):
    """``(tau (b, s) float32, tie (b, s) int32, walked (b,) float32)`` of
    ``scores (b, s, s)`` (``index_scores``'): the ``topk``-th highest of a
    row and the highest key admitted at that value, ties to the lower key.
    A row of fewer than ``topk`` causal keys reads ``NEG_INF``: every causal
    key is above it (all rows where ``topk >= s``: nothing is selected
    away).  By the kernel ``sparse_select`` where ``kernels`` and the rows
    tile, else by ``lax.top_k``.  ``walked``: the share of the kernel's row
    blocks in which a tie bound and was walked (0 where no kernel ran)."""
    scores = jax.lax.stop_gradient(scores)
    b, s, _ = scores.shape
    walked = jnp.zeros((b,), jnp.float32)
    if topk >= s:
        tau = jnp.full((b, s), NEG_INF, jnp.float32)
        tie = jnp.full((b, s), s - 1, jnp.int32)
    elif kernels and s % _LANES == 0:
        tau, tie, walked = _select_call(scores, topk, _interpret(interpret))
    else:
        tau, tie = _select_xla(scores, topk)
    return (checkpoint_name(tau, "dsa_tau"), checkpoint_name(tie, "dsa_tie"),
            walked)


def _selected(scores, tau, tie, keys):
    """The rule of a selection on operands that broadcast against each
    other: above ``tau``, or at it and no later than ``tie``; and causal."""
    live = (scores > tau) | ((scores == tau) & (keys <= tie))
    return live & (scores > NEG_INF)


def selection(scores, tau, tie):
    """The mask, int8 ``(b, s, s)``: 1 where key ``j`` is among query
    ``i``'s selected (and so at or before it), 0 everywhere else — past
    the diagonal too.  The XLA form: ``masks`` is the layer's."""
    keys = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return _selected(scores, tau[..., None], tie[..., None], keys
                     ).astype(jnp.int8)


# ``masks``' kernel: a grid step holds a (queries, keys) tile of the scores.
# The turned mask's tile is the float32 tile transposed in VMEM and compared
# against ``tau`` / ``tie`` laid as a ROW (Mosaic turns 32-bit tiles round,
# not int8 ones); a row's log-sum-exp is gathered over the key tiles as
# ``_fwd_kernel`` keeps ``m`` and ``l``, its count beside it.
def _mask_kernel(x_ref, tau_ref, tie_ref, tau_t_ref, tie_t_ref,
                 sel_ref, sel_t_ref, lse_ref, n_ref, m_scr, l_scr, n_scr,
                 *, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = tile

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    live_tile = ki * bk - qi * bq < bq

    @pl.when(live_tile)
    def _tile():
        x = x_ref[...]
        first = ki * bk
        live = _selected(
            x, tau_ref[...], tie_ref[...],
            first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
        sel_ref[...] = live.astype(jnp.int8)
        sel_t_ref[...] = _selected(
            x.T, tau_t_ref[...], tie_t_ref[...],
            first + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        ).astype(jnp.int8)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(jnp.where(live, x, NEG_INF), axis=-1,
                                       keepdims=True))
        p = jnp.where(live, jnp.exp(x - m_new[:, :1]), 0.0)
        l_scr[...] = l_scr[...] * jnp.exp(m - m_new) + jnp.sum(
            p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        n_scr[...] += jnp.sum(live.astype(jnp.float32), axis=-1,
                              keepdims=True)

    @pl.when(jnp.logical_not(live_tile))
    def _dead():
        sel_ref[...] = jnp.zeros_like(sel_ref)
        sel_t_ref[...] = jnp.zeros_like(sel_t_ref)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _leave():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        n_ref[...] = n_scr[...].astype(jnp.int32)


def _mask_call(scores, tau, tie, interpret):
    b, s, _ = scores.shape
    bq, bk = _tile(s, INDEX_TILE)
    live = _live_k(bq, bk)
    column = pl.BlockSpec((None, bq, 1), lambda b_, i, j: (b_, i, 0))
    row = pl.BlockSpec((None, 1, bq), lambda b_, i, j: (b_, 0, i))
    lanes = pl.BlockSpec((None, bq, _LANES), lambda b_, i, j: (b_, i, 0))
    sel, sel_t, lse, n = pl.pallas_call(
        functools.partial(_mask_kernel, tile=(bq, bk)),
        grid=(b, s // bq, s // bk),
        in_specs=[pl.BlockSpec((None, bq, bk),
                               lambda b_, i, j: (b_, i, live(i, j))),
                  column, column, row, row],
        out_specs=[pl.BlockSpec((None, bq, bk), lambda b_, i, j: (b_, i, j)),
                   pl.BlockSpec((None, bk, bq), lambda b_, i, j: (b_, j, i)),
                   lanes, lanes],
        out_shape=[jax.ShapeDtypeStruct((b, s, s), jnp.int8)] * 2 + [
            jax.ShapeDtypeStruct((b, s, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, s, _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32)] * 3,
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="sparse_mask",
    )(scores, tau[..., None], tie[..., None], tau[:, None], tie[:, None])
    return sel, sel_t, lse[..., 0], n[..., 0]


def masks(scores, tau, tie, *, kernels: bool = True,
          interpret: Optional[bool] = None):
    """What the rest of a layer takes from ``scores (b, s, s)`` under
    ``select``'s ``tau``, ``tie``: ``(sel, sel_t, lse_i, pairs)`` — the mask
    (``selection``'s, bit for bit), the mask with the keys first, each
    row's log-sum-exp over its selected scores ``(b, s)`` and the live pairs
    a sequence ``(b,)``.  By the kernel ``sparse_mask`` where ``kernels``
    and the tiles divide the sequence; else the mask in XLA, and ``None``
    for ``sel_t`` and ``lse_i``: ``attend`` and ``indexer_kl`` then make
    their own (``swapaxes``, ``logsumexp``)."""
    scores = jax.lax.stop_gradient(scores)
    if kernels and _fits(scores.shape[1], INDEX_TILE):
        sel, sel_t, lse_i, n = _mask_call(scores, tau, tie,
                                          _interpret(interpret))
        return sel, sel_t, lse_i, jnp.sum(n, axis=1)
    sel = selection(scores, tau, tie)
    return sel, None, None, selected_pairs(sel)


# ---------------------------------------------------------------- attention

def _grouped(q, kv_heads: int):
    """``(b, c, h, d)`` as ``(b, c, h_kv, group, d)``."""
    b, c, h, d = q.shape
    return q.reshape(b, c, kv_heads, h // kv_heads, d)


def _log2_scaled(q, sm_scale: float, prescaled: bool):
    """q as every form below multiplies it with k: times ``sm_scale x
    log2(e)`` in its own type, the flash kernels' pre-scale (``prescaled``:
    it came so), so that scores are logits in the log2 domain."""
    return q if prescaled else (q * (sm_scale * _LOG2E)).astype(q.dtype)


def _scores2(qs, k):
    """``(b, h_kv, group, c, s)`` float32 scores in the log2 domain."""
    return jnp.einsum("bckgd,bskd->bkgcs", _grouped(qs, k.shape[2]), k,
                      preferred_element_type=jnp.float32)


def _attend_xla(qs, k, v, sel):
    """The selection's softmax in XLA, a chunk of queries at a time: ``(o
    (b, s, h, dv), lse2 (b, h, s))``."""
    b, s, h, _ = qs.shape

    def one(first, q_, sel_):
        s2 = jnp.where(sel_[:, None, None] != 0, _scores2(q_, k), NEG_INF)
        lse2 = jax.nn.logsumexp(s2 * _LN2, axis=-1) * _LOG2E
        p = jnp.exp2(s2 - lse2[..., None])
        o = jnp.einsum("bkgcs,bskd->bckgd", p.astype(v.dtype), v)
        return (o.reshape(b, q_.shape[1], h, -1),
                jnp.moveaxis(lse2.reshape(b, h, -1), 1, 2))

    o, lse2 = _by_chunks(one, _chunk(s, 4 * b * h * s), qs, sel)
    # kept by the layer checkpoint under the flash kernels' names
    return (checkpoint_name(o, "flash_out"),
            checkpoint_name(jnp.moveaxis(lse2, 1, 2), "flash_lse"))


def _flash_call(q, k, v, sel, sel_t, sm_scale, tiles, interpret, prescaled):
    """``flash_fwd_dsa`` on the model's q, k, v: the output where it stands,
    the log-sum-exp a row, and the backward pass's residuals (``sel_t``,
    the mask with the keys first, among them: ``None`` where the backward
    is to turn ``sel`` round itself)."""
    in_place = attention._in_place(q, v)
    heads = (q.shape[2], k.shape[2]) if in_place else None
    qt, kt, vt = (attention._enter(x, in_place) for x in (
        _log2_scaled(q, sm_scale, prescaled), k, v))
    ot, lse = attention._fwd_call(qt, kt, vt, True, tiles, interpret, None,
                                  heads, sel)
    ot = checkpoint_name(ot, "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return (attention._leave(ot, q.shape[2]), lse,
            (qt, kt, vt, ot, lse, sel if sel_t is None else None, sel_t))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, sel, sel_t, sm_scale, tiles, interpret, prescaled):
    return _flash_call(q, k, v, sel, sel_t, sm_scale, tiles, interpret,
                       prescaled)[:2]


def _flash_fwd(q, k, v, sel, sel_t, sm_scale, tiles, interpret, prescaled):
    o, lse, res = _flash_call(q, k, v, sel, sel_t, sm_scale, tiles,
                              interpret, prescaled)
    return (o, lse), res


def _flash_bwd(sm_scale, tiles, interpret, prescaled, res, cts):
    do, _ = cts        # the log-sum-exp goes to the detached target alone
    qt, kt, vt, ot, lse, sel, sel_t = res
    in_place = qt.ndim == 3
    h = do.shape[2]
    h_kv = kt.shape[2] * h // qt.shape[2] if in_place else kt.shape[1]
    dq_scale = (sm_scale / attention.q_prescale(sm_scale, qt.dtype)
                if prescaled else sm_scale)
    dqt, dkt, dvt = attention._bwd_call(
        qt, kt, vt, ot, lse, attention._enter(do, in_place), dq_scale, True,
        tiles, interpret, None, (h, h_kv) if in_place else None,
        jnp.swapaxes(sel, 1, 2) if sel_t is None else sel_t)
    return (attention._leave(dqt, h), attention._leave(dkt, h_kv),
            attention._leave(dvt, h_kv), None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attend(q, k, v, sel, sel_t=None, *, sm_scale: float, flash: bool = True,
           q_prescaled: bool = False, interpret: Optional[bool] = None):
    """Softmax attention over the pairs ``sel (b, s, s)`` marks.  q ``(b, s,
    h, d)``, k ``(b, s, h_kv, d)``, v ``(b, s, h_kv, dv)`` -> ``(o (b, s,
    h, dv), lse2 (b, h, s))``, the log-sum-exp of the scores TIMES
    ``log2(e)``.  ``sel_t``: the mask with the keys first (``masks``'), what
    the flash backward reads; without it the backward turns ``sel`` round.
    ``q_prescaled``: q comes times the flash kernels' pre-scale
    (``attention.q_prescale``)."""
    s = q.shape[1]
    tiles = attention.choose_tiles(
        s, s, True, max(q.shape[-1], v.shape[-1]), q.dtype) if flash else None
    if tiles is not None and min(tiles) < 128:
        tiles = None    # an int8 tile is (32, 128): the kernels take whole
    if tiles is None:
        return _attend_xla(_log2_scaled(q, sm_scale, q_prescaled), k, v, sel)
    return _flash(q, k, v, sel, sel_t, sm_scale, tiles,
                  _interpret(interpret), q_prescaled)


# --------------------------------------------------------- the indexer's loss

@jax.custom_vjp
def _kl_rows(scores, sel, target):
    return _kl_rows_fwd(scores, sel, target)[0]


def _kl_rows_fwd(scores, sel, target):
    """``KL(target || softmax over the selection of scores)`` a row ``(b,
    s)``; the residual is its gradient to the scores."""
    live = sel != 0
    logits = jnp.where(live, scores, NEG_INF)
    logq = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    kl = jnp.sum(jnp.where(live, jax.scipy.special.xlogy(target, target)
                           - target * logq, 0.0), axis=-1)
    return kl, jnp.where(live, jnp.exp(logq) - target, 0.0)


def _kl_rows_bwd(grad, g):
    return (grad * g[..., None], None, None)


_kl_rows.defvjp(_kl_rows_fwd, _kl_rows_bwd)


def mean_probabilities(qs, k, lse2, sel):
    """``(b, s, s)`` float32: the heads' attention probabilities over the
    selection, remade from q (``_log2_scaled``), k and each row's
    log-sum-exp, summed over the heads and divided by their count."""
    b, s, h, _ = qs.shape

    def one(first, q_, lse_, sel_):
        lse_ = jnp.moveaxis(lse_, 1, 2).reshape(b, k.shape[2], -1, q_.shape[1])
        p = jnp.exp2(_scores2(q_, k) - lse_[..., None])
        return jnp.where(sel_ != 0, jnp.sum(p, axis=(1, 2)) / h, 0.0)

    return _by_chunks(one, _chunk(s, 4 * b * h * s), qs,
                      jnp.moveaxis(lse2, 1, 2), sel)


# The same in ONE kernel where a head fills whole lane blocks: a grid step
# holds a (queries, keys) tile, walks the heads over it — each head's
# scores and probabilities made in VMEM and summed there, so the (b, s, s)
# target never stands in HBM —, and writes the KL's terms a row and the
# gradient to the index scores' tile.
LOSS_TILE = (256, 512)


def _loss_kernel(q_ref, k_ref, lse_ref, idx_ref, sel_ref, lse_i_ref,
                 kl_ref, g_ref, kl_scr, *, heads, kv_heads, d, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = tile

    @pl.when(ki == 0)
    def _init():
        kl_scr[...] = jnp.zeros_like(kl_scr)

    live_tile = ki * bk <= qi * bq + bq - 1

    @pl.when(live_tile)
    def _tile():
        rep = heads // kv_heads
        acc = jnp.zeros((bq, bk), jnp.float32)
        for g in range(kv_heads):
            k_g = k_ref[:, g * d:(g + 1) * d]
            for r in range(rep):
                h = g * rep + r
                s2 = jax.lax.dot_general(
                    q_ref[:, h * d:(h + 1) * d], k_g,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = acc + jnp.exp2(s2 - lse_ref[:, h:h + 1])
        live = sel_ref[...] != 0
        target = jnp.where(live, acc * (1.0 / heads), 0.0)
        logq = jnp.where(live, idx_ref[...] - lse_i_ref[:, :1], 0.0)
        terms = jnp.where(
            target > 0.0, target * jnp.log(jnp.maximum(target, 1e-37)), 0.0
        ) - target * logq
        kl_scr[...] += jnp.broadcast_to(
            jnp.sum(terms, axis=-1, keepdims=True), kl_scr.shape)
        g_ref[...] = jnp.where(live, jnp.exp(logq) - target, 0.0)

    @pl.when(jnp.logical_not(live_tile))
    def _dead():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _leave():
        kl_ref[...] = kl_scr[...]


def _loss_call(qs, k, lse2, scores, sel, lse_i, interpret):
    """``(kl (b, s), the KL's gradient to the scores (b, s, s))`` by the
    kernel ``sparse_loss``; qs ``(b, s, h, d)`` in the log2 domain, ``lse_i
    (b, s)`` the selected scores' log-sum-exp a row (``None``: made here)."""
    b, s, heads, d = qs.shape
    kv_heads = k.shape[2]
    bq, bk = _tile(s, LOSS_TILE)
    if lse_i is None:
        lse_i = jax.nn.logsumexp(jnp.where(sel != 0, scores, NEG_INF),
                                 axis=-1)
    live = _live_k(bq, bk)
    at_k = lambda b_, i, j: (b_, live(i, j), 0)
    at_qk = lambda b_, i, j: (b_, i, live(i, j))
    at_q = lambda b_, i, j: (b_, i, 0)
    kl, g = pl.pallas_call(
        functools.partial(_loss_kernel, heads=heads, kv_heads=kv_heads, d=d,
                          tile=(bq, bk)),
        grid=(b, s // bq, s // bk),
        in_specs=[pl.BlockSpec((None, bq, heads * d), at_q),
                  pl.BlockSpec((None, bk, kv_heads * d), at_k),
                  pl.BlockSpec((None, bq, heads), at_q),
                  pl.BlockSpec((None, bq, bk), at_qk),
                  pl.BlockSpec((None, bq, bk), at_qk),
                  pl.BlockSpec((None, bq, _LANES), at_q)],
        out_specs=[pl.BlockSpec((None, bq, _LANES), at_q),
                   pl.BlockSpec((None, bq, bk), lambda b_, i, j: (b_, i, j))],
        out_shape=[jax.ShapeDtypeStruct((b, s, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, s, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="sparse_loss",
    )(qs.reshape(b, s, -1), k.reshape(b, s, -1), jnp.moveaxis(lse2, 1, 2),
      scores, sel, jnp.broadcast_to(lse_i[..., None], (b, s, _LANES)))
    return kl[..., 0], g


def indexer_kl(scores, sel, q, k, lse2, *, sm_scale: float,
               q_prescaled: bool = False):
    """The indexer's loss a row, ``(b, s)``: ``KL(p_t || softmax over S_t of
    I[t, .])`` with ``p_t`` detached; its gradient reaches ``scores``
    alone.  The plain form, in XLA, under autodiff: what ``kl_and_gradient``
    and the layer's rule are held to (``tests/test_keye.py``)."""
    qs, k, lse2 = jax.lax.stop_gradient(
        (_log2_scaled(q, sm_scale, q_prescaled), k, lse2))
    return _kl_rows(scores, sel, mean_probabilities(qs, k, lse2, sel))


def kl_and_gradient(scores, sel, q, k, lse2, lse_i=None, *, sm_scale: float,
                    flash: bool = True, q_prescaled: bool = False,
                    interpret: Optional[bool] = None):
    """``indexer_kl``'s rows ``(b, s)`` AND their sum's gradient to the
    scores ``(b, s, s)`` float32 (``softmax_S(I) - p`` on the selection, 0
    off it), both values of the forward pass on detached operands.  By the
    kernel ``sparse_loss`` where the flash kernels are the attention and a
    head fills whole lane blocks (``lse_i``: ``masks``' log-sum-exp of a
    row's selected scores; without it the kernel's call makes it in XLA),
    else in XLA."""
    s, d = q.shape[1], q.shape[-1]
    scores, qs, k, lse2 = jax.lax.stop_gradient(
        (scores, _log2_scaled(q, sm_scale, q_prescaled), k, lse2))
    if flash and d % _LANES == 0 and _fits(s, LOSS_TILE):
        return _loss_call(qs, k, lse2, scores, sel, lse_i,
                          _interpret(interpret))
    return _kl_rows_fwd(scores, sel, mean_probabilities(qs, k, lse2, sel))


def selected_pairs(sel):
    """Live pairs a sequence ``(b,)``: whole numbers, summed as such."""
    return jnp.sum(sel.astype(jnp.int32), axis=(1, 2))
