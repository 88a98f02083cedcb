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
- ``moe_dispatch``: a stable sort of the ``T * k`` (token, choice) pairs
  by expert that carries each pair's gate along, its inverse by a second
  sort, a gather to ``(T * k, d)`` rows;
- ``moe_experts``: three grouped products over the ragged groups (row
  ``r`` meets the weights of the group it lies in) and SwiGLU;
- ``moe_combine``: each token's rows gathered back, weighted by its
  gates, summed, and added to the residual.

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
weights' gradient, per group).  Both walk one schedule of (group, row
tile) visits handed over as scalar prefetch: a tile that two groups
share is visited once for each, and the rows that are not the visit's
are masked.  Rows past the groups' sum — the padding, and the rows of
experts that are not held here: other ranks' under expert parallelism,
other chips' where this chip holds its share of a layer (``first_expert``
and the leading dimension of the expert tensors say which) — come back as
zeros.  The row buffer is static, ``T * k`` rows however few are held.

Inside a ``shard_map`` (a Pallas kernel has no partitioning rule) the
layer takes the names of the mesh axes: tokens are split over
``token_axes`` and the same on every other axis; each rank of
``expert_axis`` holds ``E / ep`` experts, computes the rows routed to
them, and the partial outputs are summed over ``sum_axes``.
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
from ray_tpu.ops.layers import rms_norm, swiglu

# Row tile of the grouped product.  A tile that two groups share is
# computed once for each, so a call of G groups executes up to
# (tiles + G - 1) * tile rows: the largest tile that keeps this within
# MAX_EXECUTED of the rows wins (longer products feed the MXU better),
# else the smallest.  Measured on TPU v5e, bf16 (PERF.md §6, PR 25).
ROW_TILES = (512, 256, 128)
MAX_EXECUTED = 1.15
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024   # one group's weights in VMEM
_ACC_BLOCK_BYTES = 8 * 1024 * 1024      # float32 accumulator of moe_tgmm


def executed_rows(rows: int, groups: int, tile: int) -> int:
    """Rows the kernels compute at most for ``rows`` needed ones."""
    return (-(-rows // tile) + groups - 1) * tile


def choose_tiles(rows: int, groups: int) -> int:
    """The row tile for ``rows`` rows in ``groups`` groups."""
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
    prefetch.  The rows past the groups' sum are group ``G``, which has no
    weights: its tiles are zero-filled."""
    offsets: jax.Array     # (G + 2,) first row of each group, then the end
    group_ids: jax.Array   # (V,) group of each visit
    tile_ids: jax.Array    # (V,) row tile of each visit
    num_visits: jax.Array  # (1,) visits that are real; the rest is padding


def make_schedule(group_sizes: jax.Array, rows: int, tile: int) -> Schedule:
    """Visits in row order: each group walks the tiles its rows touch
    (an empty group keeps one visit, so its weight gradient is zeroed).
    ``V = rows / tile + G`` bounds their number: every tile once, and once
    more for each boundary inside a tile."""
    groups = group_sizes.shape[0]
    n_tiles = rows // tile
    group_sizes = group_sizes.astype(jnp.int32)
    sizes = jnp.concatenate(
        [group_sizes, rows - jnp.sum(group_sizes, keepdims=True)])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tile, first)
    visits = last - first + 1
    visit_ends = jnp.cumsum(visits)
    n_visits = n_tiles + groups
    group_ids = jnp.repeat(jnp.arange(groups + 1, dtype=jnp.int32), visits,
                           total_repeat_length=n_visits)
    nth = jnp.arange(n_visits, dtype=jnp.int32) - (
        visit_ends - visits)[group_ids]
    tile_ids = jnp.minimum(first[group_ids] + nth, n_tiles - 1)
    return Schedule(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32),
        group_ids, tile_ids.astype(jnp.int32),
        visit_ends[-1:].astype(jnp.int32))


def _visit(sched_refs, i, tile):
    """(group, first and one-past-last row of the group inside the
    visit's tile, is the visit real) of visit ``i``."""
    offsets, group_ids, tile_ids, num_visits = sched_refs
    g = group_ids[i]
    row0 = tile_ids[i] * tile
    return g, offsets[g] - row0, offsets[g + 1] - row0, i < num_visits[0]


def _row_mask(lo, hi, tile):
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return (rows >= lo) & (rows < hi)


def _gmm_kernel(offsets, group_ids, tile_ids, num_visits, lhs_ref, rhs_ref,
                out_ref, *, tile, groups, transpose_rhs):
    g, lo, hi, real = _visit((offsets, group_ids, tile_ids, num_visits),
                             pl.program_id(1), tile)
    live = real & (hi > lo)
    interior = (lo <= 0) & (hi >= tile)

    def store(value):
        @pl.when(interior)
        def _whole():
            out_ref[...] = value()

        @pl.when(jnp.logical_not(interior))
        def _masked():
            out_ref[...] = jnp.where(_row_mask(lo, hi, tile), value(),
                                     out_ref[...])

    @pl.when(live & (g < groups))
    def _product():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        store(lambda: jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype))

    @pl.when(live & (g == groups))
    def _past_the_groups():
        store(lambda: jnp.zeros(out_ref.shape, out_ref.dtype))


def _tgmm_kernel(offsets, group_ids, tile_ids, num_visits, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tile, groups):
    i = pl.program_id(1)
    g, lo, hi, real = _visit((offsets, group_ids, tile_ids, num_visits),
                             i, tile)
    real = real & (g < groups)
    # The pseudo-group's visit follows the last group's, so i + 1 exists.
    before = group_ids[jnp.maximum(i - 1, 0)]
    after = group_ids[jnp.minimum(i + 1, pl.num_programs(1) - 1)]

    @pl.when(real & ((i == 0) | (before != g)))
    def _first_visit():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(rhs):
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    interior = (lo <= 0) & (hi >= tile)

    @pl.when(real & (hi > lo) & interior)
    def _whole():
        accumulate(rhs_ref[...])

    @pl.when(real & (hi > lo) & jnp.logical_not(interior))
    def _masked():  # other groups' rows leave through ONE operand
        rhs = rhs_ref[...]
        accumulate(jnp.where(_row_mask(lo, hi, tile), rhs,
                             jnp.zeros_like(rhs)))

    @pl.when(real & (after != g))
    def _last_visit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    # Visits revisit output blocks in order: sequential.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


def _gmm(lhs, rhs, sched: Schedule, tile, transpose_rhs, interpret):
    """``out[r] = lhs[r] @ rhs[group of r]`` (``rhs[g].T`` if
    ``transpose_rhs``); zeros for the rows past the groups."""
    rows, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _fit_columns(n, k, rhs.dtype.itemsize, _WEIGHT_BLOCK_BYTES)
    group_of = lambda j, i, o, g, t, v: jnp.minimum(g[i], groups - 1)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, i, *s: (group_of(j, i, *s), j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, i, *s: (group_of(j, i, *s), 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=tile, groups=groups,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, sched.group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, o, g, t, v: (t[i], 0)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda j, i, o, g, t, v: (t[i], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="moe_gmm",
    )(*sched, lhs, rhs)


def _tgmm(lhs, rhs, sched: Schedule, groups, tile, interpret):
    """``out[g] = lhs[rows of g].T @ rhs[rows of g]``; zeros for an
    empty group."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    tn = _fit_columns(n, k, 4, _ACC_BLOCK_BYTES)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile=tile, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, sched.group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, o, g, t, v: (t[i], 0)),
                pl.BlockSpec((tile, tn), lambda j, i, o, g, t, v: (t[i], j))],
            out_specs=pl.BlockSpec(
                (None, k, tn),
                lambda j, i, o, g, t, v: (jnp.minimum(g[i], groups - 1), 0,
                                          j)),
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
    ``tile``).  Rows past the groups' sum give zeros and no gradient."""
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


# Dispatch and combine are gathers both ways: the transpose of "row r
# reads token t" is "token t reads its k rows", so neither direction
# scatters (a TPU scatter-add serialises; a row gather streams).  Every
# index is in range by construction (``_row_index``) and says so: a
# gather that may be out of range is followed by a pass over its whole
# result that fills the rows that were.

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
    they lie past the groups' sum, where the schedule's pseudo-group
    zero-fills what the kernels write and masks what they read."""
    n, k = flat.shape[0], gates.shape[1]
    slots = jnp.arange(n, dtype=jnp.int32)
    _, order, row_gate = jax.lax.sort(
        (flat, slots, jax.lax.stop_gradient(gates).reshape(-1)),
        num_keys=1, is_stable=True)
    row_slot = jnp.pad(order, (0, rows - n))
    slot_row = _place(slots, order)
    return (row_slot // k, row_slot, slot_row.reshape(-1, k),
            jnp.pad(row_gate, (0, rows - n)))


@jax.custom_vjp
def _dispatch(x, row_token, slot_row):
    """``x (T, d)`` -> rows ``(M, d)``: row ``r`` is ``x[row_token[r]]``;
    ``slot_row (T, k)`` is the row of each (token, choice)."""
    return _take(x, row_token)


def _dispatch_fwd(x, row_token, slot_row):
    return _dispatch(x, row_token, slot_row), slot_row


def _dispatch_bwd(slot_row, d_rows):
    d_x = jnp.sum(_take(d_rows, slot_row).astype(jnp.float32), axis=1)
    return d_x.astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y_rows, gates, row_token, row_slot, slot_row, row_gate):
    """``out[t] = sum_j gates[t, j] * y_rows[slot_row[t, j]]`` in
    float32; ``row_slot (M,)`` is the flat (token, choice) of each row
    and ``row_gate (M,)`` its gate, which the gradient reads."""
    picked = _take(y_rows, slot_row).astype(jnp.float32)
    return jnp.einsum("tk,tkd->td", gates, picked).astype(y_rows.dtype)


def _combine_fwd(y_rows, gates, row_token, row_slot, slot_row, row_gate):
    return (_combine(y_rows, gates, row_token, row_slot, slot_row, row_gate),
            (y_rows, gates, row_token, row_slot, row_gate))


def _combine_bwd(res, d_out):
    y_rows, gates, row_token, row_slot, row_gate = res
    n = gates.size
    # One gather serves both gradients: the gate's is each row's product
    # with ITS token's cotangent, taken in row order and then put back
    # in (token, choice) order.  The rows past ``n`` get gate 0.
    d_out_rows = _take(d_out, row_token).astype(jnp.float32)
    d_rows = (d_out_rows * row_gate[:, None]).astype(y_rows.dtype)
    d_row_gate = jnp.sum(y_rows.astype(jnp.float32) * d_out_rows, axis=-1)
    d_gates = _place(d_row_gate[:n], row_slot[:n]).reshape(gates.shape)
    return d_rows, d_gates.astype(gates.dtype), None, None, None, None


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
              w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
              select_bias: Optional[jax.Array] = None, *,
              num_selected: int, norm_eps: float = 1e-6,
              norm_topk_prob: bool = False, tile: Optional[int] = None,
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
    reached none: 0) and ``held_share`` (the assignments to held experts
    over all of them), beside ``counts (E,)``, every expert's assignments.
    ``router_w (d, E)``; ``w_gate``/``w_up (E', d, m')``, ``w_down (E',
    m', d)`` — all the experts, or the ``E'`` of them from ``first_expert``
    on that THIS chip holds of a layer divided over several (the router
    keeps its ``E`` outputs and ``num_selected`` a token; a row routed to
    an absent expert is computed nowhere and added nowhere), or inside a
    ``shard_map`` this rank's ``E / ep`` of them (``expert_axis``) at this
    rank's slice of ``m`` (``sum_axes`` then names the axes the partial
    outputs are summed over, and ``token_axes`` those the tokens are split
    over).  ``scoring``: ``softmax`` over the experts, or ``sigmoid`` of
    each logit; ``select_bias (E,)`` is added to the scores for the
    SELECTION only, the gates are the scores themselves and no gradient
    reaches it (``update_selection_bias`` moves it); the gates, renormalised
    where ``norm_topk_prob``, are multiplied by ``gate_scale``."""
    shape, d = x.shape, x.shape[-1]
    x = x.reshape(-1, d)
    t, e, k = x.shape[0], router_w.shape[1], num_selected
    local = w_gate.shape[0]

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
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        if gate_scale != 1.0:
            gates = gates * gate_scale
        flat = experts.reshape(-1)
        # of this shard's tokens; a compare and a sum, where ``bincount``
        # adds into its bins one element at a time
        assigned = jnp.sum(flat[:, None] == jnp.arange(e, dtype=flat.dtype),
                           axis=0, dtype=jnp.int32)
        counts = _psum(assigned, token_axes)
        tokens = _psum(jnp.float32(t), token_axes)
        mean_prob = _psum(jnp.sum(probs, axis=0), token_axes) / tokens
        aux = e * jnp.sum(counts.astype(jnp.float32) / tokens * mean_prob)
        z = _psum(jnp.sum(jnp.square(
            jax.nn.logsumexp(logits, axis=-1))), token_axes) / tokens
        load = jnp.max(counts).astype(jnp.float32) * e / (tokens * k)

    with jax.named_scope("moe_dispatch"):
        group_sizes = assigned
        ranks = (expert_axis,) if expert_axis else ()
        if local < e:  # the experts held here sort first
            first = first_expert
            if expert_axis is not None:
                first = first + jax.lax.axis_index(expert_axis) * local
            flat = (flat - first) % e
            group_sizes = jax.lax.dynamic_slice(assigned, (first,), (local,))
        tile = tile or choose_tiles(t * k, local)
        rows = -(-t * k // tile) * tile
        sched = make_schedule(group_sizes, rows, tile)
        row_token, row_slot, slot_row, row_gate = _row_index(
            flat, gates, rows)
        # What a layer checkpoint keeps (SAVED_RESIDUALS): with the row
        # index held, the rematerialised forward runs no sort.
        sched, row_token, row_slot, slot_row, row_gate = checkpoint_name(
            (sched, row_token, row_slot, slot_row, row_gate),
            "moe_row_index")
        x_rows = _dispatch(h, row_token, slot_row)
        # rows the schedule gives a held expert, against the choices that
        # name one (all of them where every expert is held somewhere)
        reached = _psum(jnp.sum(group_sizes).astype(jnp.float32),
                        tuple(token_axes) + ranks)
        held = local * (jax.lax.psum(1, ranks) if ranks else 1)
        wanted = tokens * k if held == e else _psum(jnp.sum(
            (flat < local).astype(jnp.float32)), tuple(token_axes) + ranks)
        dropped = wanted - reached

    with jax.named_scope("moe_experts"):
        product = functools.partial(
            grouped_matmul, sched=sched, tile=tile,
            interpret=attention._interpret_default())  # one rule for both
        y_rows = product(swiglu(product(x_rows, w_gate),
                                product(x_rows, w_up)), w_down)

    with jax.named_scope("moe_combine"):
        y = _psum(_combine(y_rows, gates, row_token, row_slot, slot_row,
                           row_gate), sum_axes)
        out = ((x + y) if residual else y).reshape(shape)
    return out, {"aux_loss": aux, "z_loss": z, "load_max_over_mean": load,
                 "dropped": dropped, "held_share": reached / (tokens * k),
                 "counts": counts}
