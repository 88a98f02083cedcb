"""Mixture-of-experts layer: dropless token-choice routing through a
grouped matmul.

Every token goes to its ``num_selected`` best experts, whatever the
imbalance: nothing has a capacity and nothing is dropped.  The layer
(``moe_block``) is

- ``moe_route``: RMSNorm, router logits accumulated in float32, float32
  scores (a softmax over the experts, or each logit's sigmoid),
  ``lax.top_k`` of the scores — plus a selection bias where the model has
  one, which reaches the choice and not the gate — (renormalised only
  where the model says so, then scaled), the load-balancing loss over ALL
  the choices and the router z-loss;
- ``moe_latent``, where the experts work in a latent (``moe_block``'s
  ``latent``): the normed tokens projected down to ``l`` columns before
  anything moves, and each token's summed parts projected back up at the
  end; every row between the two is ``l`` wide, not ``d``;
- ``moe_dispatch``: a stable sort of the ``T * k`` (token, choice) pairs
  by expert that carries each pair's gate along, its inverse by a second
  sort, a gather to ``(T * k, w)`` rows, ``w`` the model's ``d`` or the
  latent's ``l``;
- ``moe_experts``: the experts' FFN over the ragged groups (row ``r``
  meets the weights of the group it lies in) as ONE rule, ``expert_ffn``:
  the gate and up products with SwiGLU in their kernel's epilogue, the
  down product; backward, SwiGLU's derivative in the epilogue of the
  product with the down weights transposed, the rows' gradient as one
  kernel that adds its two products, three weight gradients.  An expert
  without a gate (``relu(x W_up) ** 2 W_down``, two matrices) is the same
  rule with the square and its derivative in those epilogues, one product
  for the rows' gradient and two weight gradients.  Between the kernels
  XLA does nothing: no pass over the row buffer;
- ``moe_combine``: each token's rows gathered back, weighted by its
  gates, summed (``_token_sum``), and added to the residual.

Rows move by gathers in both directions, forward and backward, and every
gather promises that its indices are in range (``_row_index`` says why
they are), so none is followed by a pass that fills what was not.  Single
elements (a gate to its row, a gate's gradient back, a row's number to
its slot, a choice to its expert's count) are never gathered, scattered
or added one at a time: they ride in a sort, or are counted by a compare
and a sum.

All shapes are static (``T * k`` rows, rounded up to a row tile); a group
may be empty or hold every row.  The grouped product is two Pallas
kernels, chosen over ``jax.lax.ragged_dot`` by measurement on a v5e
(PERF.md §6, PR 25): ``moe_gmm`` (rows x their group's weights, also with
the weights transposed for the rows' gradient) and ``moe_tgmm`` (the
weights' gradient, per group).  ``moe_gmm`` is one body in six forms
(``_GMM_FORMS``): the plain product, ``moe_gmm_swiglu`` (two products of
one row tile and SwiGLU of their float32 accumulators), ``moe_gmm_dswiglu``
(a product and SwiGLU's derivative), ``moe_gmm_pair`` (the float32 sum
of two products), and for an expert without a gate ``moe_gmm_relu2`` (a
product and the square of its positive part) and ``moe_gmm_drelu2`` (a
product and that square's derivative).  All walk one schedule of (group,
row tile) visits handed over as scalar prefetch: a tile that two groups
share is visited once for each, and the rows that are not the visit's are
masked, in every output.

The row buffer is static, ``T * k`` rows however few are held, and the
work on it ends at the LIVE rows, the groups' sum (a value the device
has; nothing has a capacity).  (A token's choices are distinct experts,
so at most ``T * min(k, E')`` rows can ever be live: where a chip holds
FEWER experts than a token has choices — 8 held at 22 a token — the
buffer is wider than any routing can fill.  The exact bound was weighed
and left: the work follows the live rows either way, and the one program
that meets the case fits its chip with the buffer as it is, PERF.md §4,
PR 66.)  The rows past it — the padding, and the
rows of experts that are not held here: other ranks' under expert
parallelism, other chips' where this chip holds its share of a layer
(``first_expert`` and the leading dimension of the expert tensors say
which) — get no visit of the schedule and no trip of the row-side
gathers: they hold NOTHING ANYONE MAY READ UNMASKED (what the allocator
left, NaN in interpret mode).  The TOKEN side stops there too: a token's
choice of an absent expert names a row past the live ones, so the two
token-side sums (``_token_sum``) sort the choices that name a live row to
the front, token by token, fetch those rows alone, chunk by chunk, add
each token's run of them and gather the ``T`` sums; no ``(T, k, d)``
array of a row a (token, choice) is made.  Whoever can still meet a row
past the live ones (the last live tile and chunk run over) selects,
which is exact on garbage.  Where every expert is held (``E' == E``)
every row is live by construction, and the gathers are the single ones,
unmasked.

A row buffer is BORN IN THE LAYER THAT FILLS IT (``_row_buffer``): the
kernel that writes nothing takes one operand it never reads, an array the
layer has just made.  Without one the call depends on nothing, and under
``jax.grad`` of a ``lax.scan`` over several layers what depends on nothing
the loop carries is moved out of the forward loop (JAX's partial evaluation
of the scan: the compiled ``op_name`` loses its ``while/body``) and handed
in as a constant; a constant of the outer loop may not be written by the
inner one, so every layer copied two whole static buffers before its loops
over the live rows wrote a quarter of them (``copy.264`` / ``copy.270`` of
``bf16[131072, 2304]``, 5.4 ms a step each, in Mellum2's cell;
``copy.1439`` / ``copy.1445`` in JoyAI's, ``copy.455`` / ``copy.461`` in
Keye-VL's, ``copy.664`` / ``copy.670`` in Xing4's).  Each site's operand
DIFFERS, because two calls equal in operand, shape and dtype are one call
to XLA's common-subexpression pass, and one buffer under two loops is
copied again (PERF.md §6, PR 75; ``tests/test_moe_share.py`` holds both
properties on a scan's jaxpr).

Inside a ``shard_map`` (a Pallas kernel has no partitioning rule) the
layer takes the names of the mesh axes: tokens are split over
``token_axes`` AND over ``expert_axis``, whose ranks are data parallel
everywhere outside this layer, and each rank of ``expert_axis`` holds ``E /
ep`` experts.  A rank routes its own tokens; then THE EXCHANGE (scope
``moe_exchange``): an all-gather over ``expert_axis`` of the normed tokens
``(T_local, d)``, their choices and their gates brings every rank the ``T =
ep * T_local`` tokens of its group, the rank computes the rows that name
its experts (the others lie past its live rows), and a ``psum_scatter`` of
the ``(T, d)`` partial sums returns each token's parts, summed, to the rank
that owns it.  Either way ``(ep - 1) * T_local * d`` numbers a rank: what
an all-to-all of rows moves at 8 choices a token over 4 ranks, with a token
sent once a rank however many of that rank's experts it chose and no
ragged collective.  The partial outputs of a split expert width are summed
over ``sum_axes``.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.layers import rms_norm

# Row tile of the grouped product.  A tile that two groups share is
# computed once for each, so a call of G groups executes up to
# (tiles + G - 1) * tile rows: the largest tile that keeps this within
# MAX_EXECUTED of the rows wins (longer products feed the MXU better),
# else the smallest.  Measured on TPU v5e, bf16 (PERF.md §6, PR 25).
ROW_TILES = (512, 256, 128)
MAX_EXECUTED = 1.15
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024   # one group's weights in VMEM
_ACC_BLOCK_BYTES = 8 * 1024 * 1024      # float32 accumulator of moe_tgmm
# Rows a trip of a row-side gather moves (``_over_live_rows``): the trips
# run over the live rows by up to one chunk.
ROW_CHUNK = 1024


def executed_rows(rows: int, groups: int, tile: int) -> int:
    """Rows the kernels compute at most for ``rows`` needed ones."""
    return (-(-rows // tile) + groups - 1) * tile


def choose_tiles(rows: int, groups: int) -> int:
    """The row tile for ``rows`` rows in ``groups`` groups: the rows
    EXPECTED LIVE (``moe_block`` hands over ``T * k * E' / E``), not the
    buffer's — the kernels visit only those, so the buffer's rows would
    pad the estimate and pick a tile eight times a held group's share."""
    for tile in ROW_TILES:
        if executed_rows(rows, groups, tile) <= MAX_EXECUTED * rows:
            return tile
    return ROW_TILES[-1]


def _fit_columns(n: int, rows: int, itemsize: int, budget: int) -> int:
    """Widest column block of an ``(rows, n)`` operand under ``budget``
    bytes: ``n`` itself, or a divisor of it that is a multiple of 128."""
    block = n
    while rows * block * itemsize > budget and block % 256 == 0:
        block //= 2
    return block


# The residuals a layer checkpoint keeps (``models/llama.py`` hands these
# names to its policy), named in ``moe_block``'s dispatch: the row index,
# a megabyte of integers and gates that three sorts made.  Not the sorted
# rows themselves: gathering them again costs less than holding them
# (PERF.md §6, PR 28: a kept value is rounded to its own precision in a
# pass of its own behind the gather and copied into the scan's stack and
# out, 537 MB each way a layer; the gather is 0.83 ms).
SAVED_RESIDUALS = ("moe_row_index",)


class Schedule(NamedTuple):
    """The visits of one set of group sizes, as the kernels' scalar
    prefetch.  They end at the groups' sum: the rows past it have no
    group and no visit."""
    offsets: jax.Array     # (G + 1,) first row of each group, then the end
    group_ids: jax.Array   # (V,) group of each visit
    tile_ids: jax.Array    # (V,) row tile of each visit
    num_visits: jax.Array  # (1,) visits that are real: the grid's length


def make_schedule(group_sizes: jax.Array, rows: int, tile: int) -> Schedule:
    """Visits in row order: each group walks the tiles its rows touch
    (an empty group keeps one visit, so its weight gradient is zeroed).
    ``V = rows / tile + G`` bounds their number: every tile once, and once
    more for each boundary inside a tile.  The kernels' grid ends at the
    real visits (its length is the device's, like the live rows); the
    padding behind them names the last real visit's group and tile."""
    groups = group_sizes.shape[0]
    n_tiles = rows // tile
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tile, first)
    visits = last - first + 1
    visit_ends = jnp.cumsum(visits)
    n_visits = n_tiles + groups
    # A visit's group, and that group's entries, by a compare and a sum
    # over (V, G): element gathers move one element at a time.  The
    # padding counts as the last group's, whose visits (every group has
    # one) are the last real ones.
    nth = jnp.arange(n_visits, dtype=jnp.int32)
    group_ids = jnp.minimum(jnp.sum(
        nth[:, None] >= visit_ends[None, :], axis=1, dtype=jnp.int32),
        groups - 1)
    mine = group_ids[:, None] == jnp.arange(groups, dtype=jnp.int32)

    def of_group(a):
        return jnp.sum(jnp.where(mine, a[None, :], 0), axis=1)

    # no group's tiles reach past the last group's last: a scalar keeps
    # the padding in range
    tile_ids = jnp.minimum(
        of_group(first) + nth - of_group(visit_ends - visits), last[-1])
    return Schedule(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32),
        group_ids, tile_ids.astype(jnp.int32),
        visit_ends[-1:].astype(jnp.int32))


def _visit(offsets, group_ids, tile_ids, i, tile):
    """(group, first and one-past-last row of the group inside the
    visit's tile) of visit ``i``."""
    g = group_ids[i]
    row0 = tile_ids[i] * tile
    return g, offsets[g] - row0, offsets[g + 1] - row0


def _row_mask(lo, hi, tile):
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return (rows >= lo) & (rows < hi)


# The forms of ``moe_gmm``: a call's operands in order (``r`` a tile of
# rows at their whole width, ``w`` the visit's group's weight block, ``t`` a
# tile of rows at the output's column block) and the outputs it may write.
# Every product accumulates in float32 and an output is rounded once.
_GMM_FORMS = {
    "plain": ("rw", 1),      # lhs @ w
    "swiglu": ("rww", 3),    # h = silu(x @ w_gate) * (x @ w_up); then g, u
    "dswiglu": ("rwtt", 2),  # dh = d_y @ w_down^T against g, u: dg, du
    "pair": ("rwrw", 1),     # dg @ w_gate^T + du @ w_up^T
    "relu2": ("rw", 2),      # no gate: h = relu(x @ w_up) ** 2; then u
    "drelu2": ("rwt", 1),    # dh = d_y @ w_down^T against u: du
}


def _gmm_kernel(offsets, group_ids, tile_ids, num_visits, *refs, tile, form,
                transpose_rhs, n_out):
    ins, outs = refs[:-n_out], refs[-n_out:]
    _, lo, hi = _visit(offsets, group_ids, tile_ids, pl.program_id(1), tile)
    f32 = jnp.float32

    def dot(lhs_ref, rhs_ref):
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        return jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                   preferred_element_type=f32)

    def values():
        if form == "plain":
            return (dot(ins[0], ins[1]),)
        if form == "swiglu":
            g, u = dot(ins[0], ins[1]), dot(ins[0], ins[2])
            return (jax.nn.silu(g) * u, g, u)[:n_out]
        if form == "dswiglu":
            dh = dot(ins[0], ins[1])
            g, u = ins[2][...].astype(f32), ins[3][...].astype(f32)
            s = jax.nn.sigmoid(g)
            # d silu(g) = s (1 + g (1 - s))
            return dh * u * (s * (1.0 + g * (1.0 - s))), dh * (g * s)
        if form == "relu2":
            u = dot(ins[0], ins[1])
            return (jnp.square(jnp.maximum(u, 0.0)), u)[:n_out]
        if form == "drelu2":
            u = ins[2][...].astype(f32)
            return (dot(ins[0], ins[1]) * (2.0 * jnp.maximum(u, 0.0)),)
        return (dot(ins[0], ins[1]) + dot(ins[2], ins[3]),)

    # ONE copy of the products, whatever the visit: a select against the
    # block costs nothing beside them (PERF.md §6, PR 47), and a second
    # copy for the tiles one group fills is code.  Another group's rows
    # keep what its visit wrote; the rows past the last group keep what the
    # block held (nothing: the module docstring), and what an operand tile
    # holds there (``g``, ``u``: anything) reaches no row that is read.
    @pl.when(hi > lo)  # an empty group's visit writes nothing
    def _visit_rows():
        mask = _row_mask(lo, hi, tile)
        for out_ref, new in zip(outs, values()):
            out_ref[...] = jnp.where(mask, new.astype(out_ref.dtype),
                                     out_ref[...])


def _tgmm_kernel(offsets, group_ids, tile_ids, num_visits, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tile):
    i = pl.program_id(1)
    g, lo, hi = _visit(offsets, group_ids, tile_ids, i, tile)
    # The last group's last visit is the grid's last: no visit follows it.
    before = group_ids[jnp.maximum(i - 1, 0)]
    after = group_ids[jnp.minimum(i + 1, num_visits[0] - 1)]
    last = (i + 1 == num_visits[0]) | (after != g)

    @pl.when((i == 0) | (before != g))
    def _first_visit():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(lhs, rhs):
        acc_ref[...] += jax.lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    interior = (lo <= 0) & (hi >= tile)

    @pl.when((hi > lo) & interior)
    def _whole():
        accumulate(lhs_ref[...], rhs_ref[...])

    @pl.when((hi > lo) & jnp.logical_not(interior))
    def _masked():
        # Out of BOTH operands: another group's rows are numbers and one
        # zero factor would do, but the rows past the last group may hold
        # anything, and 0 x NaN is NaN.
        mask = _row_mask(lo, hi, tile)
        lhs, rhs = lhs_ref[...], rhs_ref[...]
        accumulate(jnp.where(mask, lhs, jnp.zeros_like(lhs)),
                   jnp.where(mask, rhs, jnp.zeros_like(rhs)))

    @pl.when(last)
    def _last_visit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    # Visits revisit output blocks in order: sequential.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


def _gmm_call(form, operands, sched: Schedule, tile, interpret, *,
              transpose_rhs=False, n_out=1):
    """One ``moe_gmm`` kernel in ``form`` (``_GMM_FORMS``) over the
    schedule's visits: ``n_out`` arrays of ``(rows, n)``, whose rows past
    the groups are not written.  A call holds up to two weight blocks,
    each under ``_WEIGHT_BLOCK_BYTES`` and double-buffered: 16 MB of the
    100 the compiler is given, whatever the form."""
    kinds, most = _GMM_FORMS[form]
    assert len(operands) == len(kinds) and 1 <= n_out <= most
    rows, k = operands[0].shape
    weights = operands[1]
    n = weights.shape[1] if transpose_rhs else weights.shape[2]
    tn = _fit_columns(n, k, weights.dtype.itemsize, _WEIGHT_BLOCK_BYTES)
    tiles = pl.BlockSpec((tile, tn), lambda j, i, o, g, t, v: (t[i], j))
    specs = {
        "r": pl.BlockSpec((tile, k), lambda j, i, o, g, t, v: (t[i], 0)),
        "w": (pl.BlockSpec((None, tn, k),
                           lambda j, i, o, g, t, v: (g[i], j, 0))
              if transpose_rhs else
              pl.BlockSpec((None, k, tn),
                           lambda j, i, o, g, t, v: (g[i], 0, j))),
        "t": tiles}
    out = jax.ShapeDtypeStruct((rows, n), operands[0].dtype)
    # The ``t`` operands give their buffers to the outputs, in order: a
    # visit has read its tile of them before it writes one (a tile two
    # groups share stays in VMEM between its visits), and XLA could write
    # SwiGLU's gradient over its inputs too.
    given = [4 + i for i, kind in enumerate(kinds) if kind == "t"]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=tile, form=form,
                          transpose_rhs=transpose_rhs, n_out=n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, sched.num_visits[0]),
            in_specs=[specs[kind] for kind in kinds],
            out_specs=[tiles] * n_out),
        out_shape=[out] * n_out,
        input_output_aliases=dict(zip(given, range(n_out))),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="moe_gmm" if form == "plain" else f"moe_gmm_{form}",
    )(*sched, *operands)


def _gmm(lhs, rhs, sched: Schedule, tile, transpose_rhs, interpret):
    """``out[r] = lhs[r] @ rhs[group of r]`` (``rhs[g].T`` if
    ``transpose_rhs``); the rows past the groups are not written."""
    return _gmm_call("plain", (lhs, rhs), sched, tile, interpret,
                     transpose_rhs=transpose_rhs)[0]


def _tgmm(lhs, rhs, sched: Schedule, groups, tile, interpret):
    """``out[g] = lhs[rows of g].T @ rhs[rows of g]``; zeros for an
    empty group.  The rows past the groups are not read."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    tn = _fit_columns(n, k, 4, _ACC_BLOCK_BYTES)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, sched.num_visits[0]),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, o, g, t, v: (t[i], 0)),
                pl.BlockSpec((tile, tn), lambda j, i, o, g, t, v: (t[i], j))],
            out_specs=pl.BlockSpec(
                (None, k, tn), lambda j, i, o, g, t, v: (g[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="moe_tgmm",
    )(*sched, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(rows: jax.Array, weights: jax.Array, sched: Schedule,
                   tile: int, interpret: bool) -> jax.Array:
    """``rows (M, K)`` x ``weights (G, K, N)`` -> ``(M, N)``: row ``r``
    meets ``weights[g]`` for the group ``g`` that ``sched`` puts it in
    (``make_schedule(group_sizes, M, tile)``; ``M`` a multiple of
    ``tile``).  Rows past the groups' sum are neither read nor written:
    what comes back there, and as their gradient, is unspecified.  ONE
    grouped product with its gradients; the expert layer's three are
    ``expert_ffn``, which shares these kernels and puts what lies between
    the products into them (this is what it is tested against)."""
    return _gmm(rows, weights.astype(rows.dtype), sched, tile, False,
                interpret)


def _grouped_matmul_fwd(rows, weights, sched, tile, interpret):
    return (grouped_matmul(rows, weights, sched, tile, interpret),
            (rows, weights, sched))


def _grouped_matmul_bwd(tile, interpret, res, d_out):
    rows, weights, sched = res
    d_rows = _gmm(d_out, weights.astype(d_out.dtype), sched, tile, True,
                  interpret)
    d_weights = _tgmm(rows, d_out, sched, weights.shape[0], tile, interpret)
    return d_rows, d_weights.astype(weights.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def expert_ffn(rows: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array,
               w_down: jax.Array, sched: Schedule, tile: int,
               interpret: bool) -> jax.Array:
    """The routed experts' FFN on their rows: ``(silu(rows @ w_gate[g]) *
    (rows @ w_up[g])) @ w_down[g]`` for the group ``g`` that ``sched`` puts
    a row in — or, ``w_gate`` None, the ungated ``relu(rows @ w_up[g]) ** 2
    @ w_down[g]``; ``rows (M, d)``, ``w_gate``, ``w_up (G, d, m)``,
    ``w_down (G, m, d)``.  ONE rule, no op of which reads or writes a row
    the schedule does not visit (what comes back past the groups' sum, and
    as its gradient, is unspecified):

    - forward ``moe_gmm_swiglu`` reads a row tile once for both products
      and writes ``h = silu(g) * u`` from the float32 accumulators; then
      ``moe_gmm`` with ``w_down``.  Differentiated, it writes ``g`` and
      ``u`` beside ``h``, rounded to the rows' precision, as residuals: a
      forward pass that keeps none (the first under a layer checkpoint)
      is spared them;
    - backward ``moe_gmm_dswiglu`` is ``d_y @ w_down^T`` with SwiGLU's
      derivative at ``g``, ``u`` in its epilogue (``dg``, ``du``; no
      ``dh``), ``moe_gmm_pair`` adds ``dg @ w_gate^T + du @ w_up^T`` in
      float32 before the one cast (no two cotangents of the rows to sum),
      and three ``moe_tgmm`` make the weights' gradients;
    - without a gate: forward ``moe_gmm_relu2`` writes ``h = relu(u) ** 2``
      (and ``u`` where differentiated), then ``moe_gmm``; backward
      ``moe_gmm_drelu2`` writes ``du = (d_y @ w_down^T) * 2 relu(u)`` over
      ``u``, the plain ``moe_gmm`` with ``w_up`` transposed is the rows'
      gradient, and two ``moe_tgmm`` the weights'."""
    return _ffn_forward(rows, w_gate, w_up, w_down, sched, tile, interpret,
                        differentiated=False)[0]


def _ffn_forward(rows, w_gate, w_up, w_down, sched, tile, interpret,
                 differentiated):
    """``(y, h, ...)``: the FFN's output, then the first kernel's outputs
    (``h``; ``differentiated``, behind it what its derivative is taken at:
    ``g`` and ``u``, or without a gate ``u``)."""
    cast = lambda w: w.astype(rows.dtype)
    if w_gate is None:
        form, weights = "relu2", (cast(w_up),)
    else:
        form, weights = "swiglu", (cast(w_gate), cast(w_up))
    hidden = _gmm_call(form, (rows, *weights), sched, tile, interpret,
                       n_out=_GMM_FORMS[form][1] if differentiated else 1)
    return (_gmm(hidden[0], cast(w_down), sched, tile, False, interpret),
            *hidden)


def _expert_ffn_fwd(rows, w_gate, w_up, w_down, sched, tile, interpret):
    y, h, *at = _ffn_forward(rows, w_gate, w_up, w_down, sched, tile,
                             interpret, differentiated=True)
    return y, (rows, at, h, w_gate, w_up, w_down, sched)


def _expert_ffn_bwd(tile, interpret, res, d_y):
    rows, at, h, w_gate, w_up, w_down, sched = res
    cast = lambda w: w.astype(d_y.dtype)

    def d_weights(*products):
        return (_tgmm(lhs, rhs, sched, w.shape[0], tile, interpret
                      ).astype(w.dtype) for lhs, rhs, w in products)

    if w_gate is None:
        du, = _gmm_call("drelu2", (d_y, cast(w_down), *at), sched, tile,
                        interpret, transpose_rhs=True)
        d_rows = _gmm(du, cast(w_up), sched, tile, True, interpret)
        return (d_rows, None,
                *d_weights((rows, du, w_up), (h, d_y, w_down)), None)
    dg, du = _gmm_call("dswiglu", (d_y, cast(w_down), *at), sched, tile,
                       interpret, transpose_rhs=True, n_out=2)
    d_rows, = _gmm_call("pair", (dg, cast(w_gate), du, cast(w_up)), sched,
                        tile, interpret, transpose_rhs=True)
    return (d_rows, *d_weights((rows, dg, w_gate), (rows, du, w_up),
                               (h, d_y, w_down)), None)


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


# Dispatch and combine are gathers both ways: the transpose of "row r
# reads token t" is "token t reads its k rows", so neither direction
# scatters (a TPU scatter-add serialises; a row gather streams).  Every
# index is in range by construction (``_row_index``) and says so: a
# gather that may be out of range is followed by a pass over its whole
# result that fills the rows that were.
#
# ``live`` is the groups' sum where it may be under ``T * k`` (a share of
# the experts) and None where every row is live by construction.  The two
# ROW-side gathers (``_dispatch``, ``_combine``'s gradient) then stop at
# it: a loop over chunks of rows whose trip count the device computes,
# exact at any count up to the buffer's.  The two TOKEN-side ones
# (``_combine``, ``_dispatch``'s gradient) are one sum, ``_token_sum``,
# whose work follows the live rows as well: the same loop over the live
# (token, choice) slots in token order, then one gather of ``T`` rows.

def _take(a, index):
    """``a[index]`` along axis 0, for indices that ARE in range."""
    return a.at[index].get(mode="promise_in_bounds")


def _place(values, where):
    """``out[where[i]] = values[i]`` for a permutation ``where``: one
    sort of (where, values) pairs.  A gather or scatter of single
    elements moves them one at a time, seven times slower at the
    benchmark's 131072 (PERF.md §6, PR 28)."""
    return jax.lax.sort((where, values), num_keys=1)[1]


def _row_index(flat, gates, rows):
    """The row index of ``flat (T * k,)``, the group of each (token,
    choice), sorted stably into ``rows >= T * k`` rows: ``row_token``,
    ``row_slot (rows,)`` the flat (token, choice) of each row
    (``row_token = row_slot // k``), ``slot_row (T, k)`` the row of each
    (token, choice), and ``row_gate (rows,)`` each row's gate, which
    rides in the sort (and carries no gradient: ``_combine`` returns the
    gates').  ``row_slot[:T * k]`` and ``slot_row`` are permutations of
    ``0 .. T * k - 1`` and each other's inverse.  The rows past ``T * k``
    name (token 0, choice 0) at gate 0: any index in range will do, for
    they lie past the groups' sum, where the schedule has no visit and
    the buffers hold nothing anyone may read unmasked (the module
    docstring).  So do the rows of absent experts, which sort behind the
    held ones and whose ``slot_row`` names a row there."""
    n, k = flat.shape[0], gates.shape[1]
    slots = jnp.arange(n, dtype=jnp.int32)
    _, order, row_gate = jax.lax.sort(
        (flat, slots, jax.lax.stop_gradient(gates).reshape(-1)),
        num_keys=1, is_stable=True)
    row_slot = jnp.pad(order, (0, rows - n))
    slot_row = _place(slots, order)
    return (row_slot // k, row_slot, slot_row.reshape(-1, k),
            jnp.pad(row_gate, (0, rows - n)))


def _row_buffer(shape, dtype, after):
    """A buffer nobody has written: what a loop over the live rows fills
    as far as it goes.  A kernel that writes nothing, because a
    ``jnp.zeros`` of ``(32768, 3584)`` is a pass of 0.29 ms on a v5e (three
    a layer) and 0.4 GB of the benchmark's fullest program (PERF.md §6,
    PR 39).

    ``after`` is an operand the kernel never reads (``pl.ANY``: no DMA, no
    byte moved): it makes the buffer the LAYER's, where a call that depends
    on nothing is lifted out of the forward loop over a run of layers and
    then copied whole by every layer (the module docstring; PERF.md §6, PR
    75).  Two calls with the same operand, shape and dtype are ONE call to
    the compiler's common-subexpression pass, whose one buffer two loops
    write and one of them copies, so each site hands over a DIFFERENT
    array, one its layer has made in HBM and its loop reads anyway (no
    value is materialised for the call's sake): the tokens in
    ``_dispatch``, the rows to sum in ``_live_token_sum`` (the experts'
    output forward, the rows' gradient backward), the tokens' cotangent in
    ``_combine_bwd`` — not ``y_rows`` there, which a layer whose backward
    reruns ``_combine`` hands to the rerun's sum as well."""
    return pl.pallas_call(
        lambda after_ref, out_ref: None,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=attention._interpret_default(), name="moe_row_buffer")(
            after)


def _live_trips(live, rows):
    """(trips, chunk) of a loop over the live of ``rows`` rows."""
    chunk = min(ROW_CHUNK, rows)
    return (live + chunk - 1) // chunk, chunk


def _over_live_rows(live, rows, body, carry):
    """``carry = body(start, chunk, carry)`` over chunks of ``chunk`` rows
    from row 0 until one ends at or past ``live`` (the last starts where
    it still fits the ``rows``, so it may run over rows a trip has seen: a
    body WRITES what it computes, it does not add).  The trip count is the
    device's; nothing differentiates through the loop (the callers are
    rules of a ``custom_vjp``)."""
    trips, chunk = _live_trips(live, rows)

    def trip(i, carry):
        return body(jnp.minimum(i * chunk, rows - chunk), chunk, carry)

    return jax.lax.fori_loop(0, trips, trip, carry)


def _token_sum(rows, slot_row, weights, live):
    """``out[t] = sum_j weights[t, j] * rows[slot_row[t, j]]`` over the
    choices ``j`` whose row is below ``live``: float32 products (the rows
    alone where ``weights`` is None) and sum, cast to the rows' dtype.

    Where ``live`` is None every row is live: ONE gather of a row a (token,
    choice) and the sum.  Else the work follows the live rows, and no ``(T,
    k, d)`` array is made: the live slots in (token, choice) order (one
    stable sort: a token's live choices are then a RUN of at most ``k``
    adjacent entries), their rows fetched chunk by chunk
    (``_over_live_rows``), weighted, and each entry's sum with the entries
    of its own token before it (``k - 1`` shifted, selected adds: a trip
    fetches the ``k - 1`` entries before its chunk again) written, rounded
    once, to a buffer nobody wrote; then ONE gather of ``T`` rows, the END
    of each token's run, which holds the whole sum (zeros where it has
    none).  A token's run may cross chunks; the entries past ``live``
    (dead slots, sorted behind) are computed by the last trip and read by
    nobody: a sum looks only backwards."""
    if live is None:
        picked = _take(rows, slot_row).astype(jnp.float32)
        out = (jnp.sum(picked, axis=1) if weights is None
               else jnp.einsum("tk,tkd->td", weights, picked))
        return out.astype(rows.dtype)
    return _live_token_sum(rows, slot_row, weights, live,
                           (ROW_CHUNK, attention._interpret_default()))


@functools.partial(jax.jit, static_argnums=4, inline=True)
def _live_token_sum(rows, slot_row, weights, live, traced_under):
    """``_token_sum`` of a share.  Under ``jit`` for its CACHE, not for a
    program of its own (``inline``: its equations join the caller's): the
    rules that call it are traced a dozen times a layer body, and this is
    17 ms of Python a time where the parent's gather and sum were 0.3 —
    2.7 s of ``compile.trace_s`` in the cell of five scans, 11 % of its
    set-up (PERF.md §6, PR 41).  One trace a shape now.  ``traced_under``
    is the module state the trace reads (the chunk, whether the buffer's
    kernel is interpreted): part of the cache's key, so a test that changes
    either is not handed a trace from before."""
    t, k = slot_row.shape
    f32 = jnp.float32
    n, halo = t * k, k - 1
    below = slot_row < live
    operands = (jnp.logical_not(below).reshape(-1).astype(jnp.int32),
                jnp.arange(n, dtype=jnp.int32), slot_row.reshape(-1))
    if weights is not None:
        operands += (weights.reshape(-1).astype(f32),)
    _, slot, *row_and_gate = jax.lax.sort(operands, num_keys=1,
                                          is_stable=True)
    # ``halo`` entries of no token in front: the first trip looks back too
    token = jnp.pad(slot // k, (halo, 0), constant_values=-1)
    row_and_gate = [jnp.pad(a, (halo, 0)) for a in row_and_gate]

    def runs_of(start, chunk, out):
        tok, row, *gate = (jax.lax.dynamic_slice_in_dim(a, start, chunk + halo)
                           for a in (token, *row_and_gate))
        part = _take(rows, row).astype(f32)
        if gate:
            part = part * gate[0][:, None]

        def older(back):  # a select: exact on what a dead row holds
            here = slice(halo - back, halo - back + chunk)
            return jnp.where((tok[here] == tok[halo:])[:, None], part[here],
                             0.0)

        # oldest first: a token's choices in their own order
        total = functools.reduce(
            jnp.add, [older(back) for back in range(halo, 0, -1)]
            + [part[halo:]])
        return jax.lax.dynamic_update_slice_in_dim(
            out, total.astype(rows.dtype), start, 0)

    runs = _over_live_rows(live, n, runs_of,
                           _row_buffer((n, rows.shape[1]), rows.dtype, rows))
    count = jnp.sum(below, axis=1, dtype=jnp.int32)
    end = jnp.maximum(jnp.cumsum(count) - 1, 0)
    return jnp.where((count > 0)[:, None], _take(runs, end),
                     jnp.zeros((), rows.dtype))


def _token_rows_read(live, t, k):
    """The rows ``_token_sum`` fetches for ``t`` tokens of ``k`` choices
    over those ``t * k``: its trips' chunks, each with the ``k - 1``
    entries before it, and the ``t`` at the runs' ends; 1 where every row
    is live."""
    if live is None:
        return jnp.float32(1.0)
    trips, chunk = _live_trips(live, t * k)
    return (trips * (chunk + k - 1) + t).astype(jnp.float32) / (t * k)


@jax.custom_vjp
def _dispatch(x, row_token, slot_row, live):
    """``x (T, d)`` -> rows ``(M, d)``: row ``r`` is ``x[row_token[r]]``
    for ``r < live`` (every row where ``live`` is None); ``slot_row (T,
    k)`` is the row of each (token, choice)."""
    if live is None:
        return _take(x, row_token)
    rows = row_token.shape[0]

    def gather(start, chunk, out):
        index = jax.lax.dynamic_slice_in_dim(row_token, start, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _take(x, index), start, 0)

    return _over_live_rows(live, rows, gather,
                           _row_buffer((rows, x.shape[1]), x.dtype, x))


def _dispatch_fwd(x, row_token, slot_row, live):
    return _dispatch(x, row_token, slot_row, live), (slot_row, live)


def _dispatch_bwd(res, d_rows):
    slot_row, live = res
    return _token_sum(d_rows, slot_row, None, live), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y_rows, gates, row_token, row_slot, slot_row, row_gate, live):
    """``out[t] = sum_j gates[t, j] * y_rows[slot_row[t, j]]`` in
    float32, over the choices whose row is live; ``row_slot (M,)`` is the
    flat (token, choice) of each row and ``row_gate (M,)`` its gate, which
    the gradient reads."""
    return _token_sum(y_rows, slot_row, gates, live)


def _combine_fwd(y_rows, gates, row_token, row_slot, slot_row, row_gate,
                 live):
    return (_combine(y_rows, gates, row_token, row_slot, slot_row, row_gate,
                     live),
            (y_rows, gates, row_token, row_slot, row_gate, live))


def _combine_bwd(res, d_out):
    y_rows, gates, row_token, row_slot, row_gate, live = res
    n, rows = gates.size, row_token.shape[0]

    def weigh(row_token, row_gate, y_rows):
        # One gather serves both gradients: the gate's is each row's
        # product with ITS token's cotangent, taken in row order and then
        # put back in (token, choice) order.
        d_out_rows = _take(d_out, row_token).astype(jnp.float32)
        return ((d_out_rows * row_gate[:, None]).astype(y_rows.dtype),
                jnp.sum(y_rows.astype(jnp.float32) * d_out_rows, axis=-1))

    if live is None:
        d_rows, d_row_gate = weigh(row_token, row_gate, y_rows)
    else:
        def chunk_of(start, chunk, carry):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk)
            put = lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, part, start, 0)
            d_rows, d_row_gate = weigh(cut(row_token), cut(row_gate),
                                       cut(y_rows))
            below = start + jnp.arange(chunk, dtype=jnp.int32) < live
            return (put(carry[0], d_rows),
                    put(carry[1], jnp.where(below, d_row_gate, 0.0)))

        # a row that is not live moves no gate: its gradient is 0
        d_rows, d_row_gate = _over_live_rows(
            live, rows, chunk_of,
            (_row_buffer(y_rows.shape, y_rows.dtype, d_out),
             jnp.zeros((rows,), jnp.float32)))
    d_gates = _place(d_row_gate[:n], row_slot[:n]).reshape(gates.shape)
    return d_rows, d_gates.astype(gates.dtype), None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _psum(x, axes):
    return jax.lax.psum(x, tuple(axes)) if axes else x


def update_selection_bias(bias: jax.Array, counts: jax.Array,
                          speed: float) -> jax.Array:
    """The auxiliary-loss-free balancing of arXiv:2412.19437 §2.1.2: after
    a step the selection bias of an expert whose load (``counts (..., E)``,
    its assignments in that step) was over the mean goes down by ``speed``,
    that of one under the mean up, one at the mean stays."""
    counts = counts.astype(jnp.float32)
    over = counts - jnp.mean(counts, axis=-1, keepdims=True)
    return (bias.astype(jnp.float32) - speed * jnp.sign(over)).astype(
        bias.dtype)


def moe_block(x: jax.Array, norm_w: jax.Array, router_w: jax.Array,
              w_gate: Optional[jax.Array], w_up: jax.Array, w_down: jax.Array,
              select_bias: Optional[jax.Array] = None, *,
              latent: Optional[Tuple[jax.Array, jax.Array]] = None,
              num_selected: int, norm_eps: float = 1e-6,
              norm_topk_prob: bool = False, topk_norm_eps: float = 0.0,
              tile: Optional[int] = None,
              scoring: str = "softmax", gate_scale: float = 1.0, first_expert: int = 0,
              residual: bool = True,
              token_axes: Sequence[str] = (),
              expert_axis: Optional[str] = None,
              sum_axes: Sequence[str] = ()
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expert layer on the residual stream ``x (..., d)``: returns
    ``x + experts(norm(x))`` (the experts' sum alone without ``residual``)
    and float32 scalars ``aux_loss`` (load balancing), ``z_loss``,
    ``load_max_over_mean`` (the busiest expert's assignments over the
    mean), ``dropped`` (assignments to an expert that is held and that
    reached none: 0), ``held_share`` (the assignments to held experts
    over all of them) and ``rows_visited_share`` (the rows of the tiles the
    kernels visit, a tile once for each group in it, over the buffer's
    rows) and ``token_rows_read_share`` (the rows ONE token-side sum
    fetches over the ``T * k`` (token, choice) pairs: 1 where every expert
    is held) and ``rank_rows_max_over_mean`` (the most live rows on a rank
    of ``expert_axis`` over the ranks' mean: the straggler the others wait
    for in the exchange; 1 without ranks), beside ``counts (E,)``, every
    expert's assignments.
    ``router_w (d, E)``; ``w_gate``/``w_up (E', d, m')``, ``w_down (E',
    m', d)`` (``w_gate`` None: experts without a gate, ``expert_ffn``) —
    all the experts, or the ``E'`` of them from ``first_expert``
    on that THIS chip holds of a layer divided over several (the router
    keeps its ``E`` outputs and ``num_selected`` a token; a row routed to
    an absent expert is computed nowhere and added nowhere), or inside a
    ``shard_map`` this rank's ``E / ep`` of them (``expert_axis``, over
    which the tokens are split too and exchanged: the module docstring) at
    this rank's slice of ``m`` (``sum_axes`` then names the axes the
    partial outputs are summed over, and ``token_axes`` the other axes the
    tokens are split over).  ``scoring``: ``softmax`` over the experts, or
    ``sigmoid`` of each logit; ``select_bias (E,)`` is added to the scores for the
    SELECTION only, the gates are the scores themselves and no gradient
    reaches it (``update_selection_bias`` moves it); the gates, renormalised
    where ``norm_topk_prob`` (over their sum plus ``topk_norm_eps``, which
    a model that guards the division states), are multiplied by
    ``gate_scale``.
    ``latent``: ``(w_in (d, l), w_out (l, d))`` of experts that work in a
    latent of ``l`` (their matrices ``(E', l, m')`` and ``(E', m', l)``):
    the normed tokens are projected down BEFORE the exchange and the
    dispatch, which both move ``l``-wide rows, and each token's summed parts
    up AFTER the combine, the exchange's scatter-sum and the sum over
    ``sum_axes`` (``w_out`` is linear: a partial sum's product is that
    share's part); both products under the scope ``moe_latent``.  The
    router reads the normed tokens, never the latent."""
    shape, d = x.shape, x.shape[-1]
    x = x.reshape(-1, d)
    t, e, k = x.shape[0], router_w.shape[1], num_selected
    local = w_up.shape[0]
    ranks = (expert_axis,) if expert_axis else ()
    shards = tuple(token_axes) + ranks  # the axes the tokens are split over

    with jax.named_scope("moe_route"):
        h = rms_norm(x, norm_w, norm_eps)
        logits = jnp.dot(h, router_w.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = scores = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        if select_bias is None:
            gates, experts = jax.lax.top_k(scores, k)            # (T, k)
        else:
            _, experts = jax.lax.top_k(
                scores + jax.lax.stop_gradient(select_bias).astype(
                    jnp.float32), k)
            gates = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            total = jnp.sum(gates, axis=-1, keepdims=True)
            if topk_norm_eps:  # 0 adds no op to the program
                total = total + topk_norm_eps
            gates = gates / total
        if gate_scale != 1.0:
            gates = gates * gate_scale
        flat = experts.reshape(-1)
        # of this shard's tokens; a compare and a sum, where ``bincount``
        # adds into its bins one element at a time
        assigned = jnp.sum(flat[:, None] == jnp.arange(e, dtype=flat.dtype),
                           axis=0, dtype=jnp.int32)
        counts = _psum(assigned, shards)
        tokens = _psum(jnp.float32(t), shards)
        mean_prob = _psum(jnp.sum(probs, axis=0), shards) / tokens
        aux = e * jnp.sum(counts.astype(jnp.float32) / tokens * mean_prob)
        z = _psum(jnp.sum(jnp.square(
            jax.nn.logsumexp(logits, axis=-1))), shards) / tokens
        load = jnp.max(counts).astype(jnp.float32) * e / (tokens * k)

    if latent is not None:
        with jax.named_scope("moe_latent"):
            h = h @ latent[0].astype(h.dtype)

    if expert_axis is not None:
        with jax.named_scope("moe_exchange"):
            h, experts, gates = (
                jax.lax.all_gather(a, expert_axis, axis=0, tiled=True)
                for a in (h, experts, gates))
            assigned = jax.lax.psum(assigned, expert_axis)
        # the group's tokens, rank by rank
        t, flat = h.shape[0], experts.reshape(-1)

    with jax.named_scope("moe_dispatch"):
        group_sizes = assigned
        if local < e:  # the experts held here sort first
            first = first_expert
            if expert_axis is not None:
                first = first + jax.lax.axis_index(expert_axis) * local
            flat = (flat - first) % e
            group_sizes = jax.lax.dynamic_slice(assigned, (first,), (local,))
        tile = tile or choose_tiles(t * k * local // e, local)
        rows = -(-t * k // tile) * tile
        sched = make_schedule(group_sizes, rows, tile)
        row_token, row_slot, slot_row, row_gate = _row_index(
            flat, gates, rows)
        # What a layer checkpoint keeps (SAVED_RESIDUALS): with the row
        # index held, the rematerialised forward runs no sort.
        sched, row_token, row_slot, slot_row, row_gate = checkpoint_name(
            (sched, row_token, row_slot, slot_row, row_gate),
            "moe_row_index")
        # every row is live by construction where every expert is held
        live = None if local == e else sched.offsets[local]
        x_rows = _dispatch(h, row_token, slot_row, live)
        # rows the schedule gives a held expert, against the choices that
        # name one (all of them where every expert is held somewhere)
        here = jnp.sum(group_sizes).astype(jnp.float32)
        reached = _psum(here, shards)
        held = local * (jax.lax.psum(1, ranks) if ranks else 1)
        wanted = tokens * k if held == e else _psum(jnp.sum(
            (flat < local).astype(jnp.float32)), shards)
        dropped = wanted - reached
        # the row tiles the kernels visit over the buffer's, a shard
        n_shards = jax.lax.psum(1, shards) if shards else 1
        visited = _psum(sched.num_visits[0].astype(jnp.float32) * tile / rows,
                        shards) / n_shards
        # the rows one token-side sum fetches over the (token, choice)
        # pairs, a shard
        fetched = _psum(_token_rows_read(live, t, k), shards) / n_shards
        # the fullest rank's live rows over the ranks' mean
        uneven = (jax.lax.pmax(here, shards) * n_shards
                  / jnp.maximum(reached, 1.0)) if ranks else jnp.float32(1.0)

    with jax.named_scope("moe_experts"):
        y_rows = expert_ffn(x_rows, w_gate, w_up, w_down, sched, tile,
                            attention._interpret_default())

    with jax.named_scope("moe_combine"):
        y = _combine(y_rows, gates, row_token, row_slot, slot_row, row_gate,
                     live)
    if expert_axis is not None:
        with jax.named_scope("moe_exchange"):
            y = jax.lax.psum_scatter(y, expert_axis, scatter_dimension=0,
                                     tiled=True)
    with jax.named_scope("moe_combine"):
        y = _psum(y, sum_axes)
    if latent is not None:
        with jax.named_scope("moe_latent"):
            y = y @ latent[1].astype(y.dtype)
    with jax.named_scope("moe_combine"):
        out = ((x + y) if residual else y).reshape(shape)
    return out, {"aux_loss": aux, "z_loss": z, "load_max_over_mean": load,
                 "dropped": dropped, "held_share": reached / (tokens * k),
                 "rows_visited_share": visited,
                 "token_rows_read_share": fetched,
                 "rank_rows_max_over_mean": uneven, "counts": counts}
