"""The dropless expert layer (``ops/moe.py``) against a per-token loop, its
schedule, its row index and its gathers.  CPU, float32 unless said; the
Pallas kernels run in interpret mode.  A share and its poisoned tails are
``tests/test_moe_share.py``'s, the expert FFN's one rule
``tests/test_moe_ffn.py``'s, the OLMoE-shaped model and ``ep``
``tests/test_moe_model.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from moe_layer import (
    D, E, K, NAMES, T, against_the_loop, layer_inputs, per_token_loop,
    seeded_experts, value_and_gradients)
from ray_tpu.ops import moe


@pytest.mark.parametrize("tile", [16, 128], ids=["tile16", "tile128"])
def test_layer_equals_a_per_token_loop(tile):
    """Output and the gradients to x, the norm, the router and all three
    expert tensors; 288 rows in 8 uneven groups, no multiple of a tile."""
    args = layer_inputs()
    layer = functools.partial(moe.moe_block, num_selected=K, tile=tile)
    stats, _ = against_the_loop(layer, per_token_loop, args, "every expert")
    assert float(stats["dropped"]) == 0


def test_group_sizes_sum_to_all_assignments_and_fit_no_tile():
    sizes = np.bincount(np.asarray(seeded_experts()).reshape(-1),
                        minlength=E)
    assert sizes.sum() == T * K and (sizes % 16 != 0).any()
    sched = moe.make_schedule(jnp.asarray(sizes), 288, 16)
    visits = int(sched.num_visits[0])
    groups, tiles = (np.asarray(a)[:visits] for a in sched[1:3])
    offsets = np.asarray(sched.offsets)
    assert offsets[-1] == 288 and (np.diff(offsets) == sizes).all()
    # every row of every group lies in a tile one of its visits names
    for g in range(E):
        rows = np.arange(offsets[g], offsets[g + 1])
        assert set(rows // 16) == set(tiles[groups == g])
    assert visits <= 288 // 16 + E
    assert (np.diff(tiles) >= 0).all() and (np.diff(groups) >= 0).all()


def test_tile_keeps_executed_rows_within_the_bound():
    # the benchmark cell: 131072 rows in 64 groups
    assert moe.choose_tiles(131072, 64) == 256
    assert moe.executed_rows(131072, 64, 256) <= 1.15 * 131072
    assert moe.executed_rows(131072, 64, 512) > 1.15 * 131072
    assert moe.choose_tiles(131072, 8) == 512
    assert moe.choose_tiles(256, 64) == 128  # none fits: the smallest
    # one chip's share of eight at 8192 tokens x 4 is given the rows
    # EXPECTED live, 4096 in 8 groups, and not the buffer's 32768: the
    # smallest tile, 39 visits of 128 rows for 4096 needed
    assert moe.choose_tiles(8192 * 4 * 8 // 64, 8) == 128
    assert moe.executed_rows(4096, 8, 128) == 39 * 128


def test_every_token_to_the_same_experts():
    """Adversarial routing: a router that sends every token to the same
    three experts.  Nothing is dropped, the five idle experts get exactly
    zero gradient, nothing is NaN."""
    x, norm, router, w_gate, w_up, w_down = layer_inputs()
    x = jnp.abs(x)  # so that its product with the first K columns is > 0
    router = jnp.zeros((D, E)).at[:, :K].set(1.0)
    args = (x, jnp.ones_like(norm), router, w_gate, w_up, w_down)
    (out, stats), grads = value_and_gradients(
        functools.partial(moe.moe_block, num_selected=K, tile=16), args)
    assert float(stats["dropped"]) == 0
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / K)
    assert float(jnp.abs(out - jax.jit(per_token_loop)(*args)).max()) < 5e-6
    for name, g in zip(NAMES, grads):
        assert bool(jnp.isfinite(g).all()), name
    for g in grads[3:]:
        assert float(jnp.abs(g[K:]).max()) == 0.0
        assert all(float(jnp.abs(g[e]).max()) > 0 for e in range(K))


def _real_visits(sched):
    visits = int(sched.num_visits[0])
    return (visits, np.asarray(sched.group_ids)[:visits],
            np.asarray(sched.tile_ids)[:visits])


@pytest.mark.parametrize("sizes", [
    [40, 30, 36, 22],      # every row of the buffer is live
    [5, 3, 4, 4],          # an eighth
    [0, 0, 0, 0],          # none
    [0, 128, 0, 0],        # one group holds every row
    [16, 0, 16, 9],        # the last group ends inside a tile
    [16, 16, 0, 0],        # the live rows end on a tile's edge
], ids=["all", "eighth", "none", "one_group", "inside_a_tile", "on_an_edge"])
def test_schedule_ends_at_the_held_groups_sum(sizes):
    """No visit past the live rows: at most a visit a live tile and one
    more a group, none of a group that does not exist, every held group's
    rows under exactly the visits that name it, and the padding names the
    last real visit again, so the pipeline moves no block for it."""
    rows, tile, groups = 128, 16, len(sizes)
    sched = moe.make_schedule(jnp.asarray(sizes), rows, tile)
    visits, group_ids, tile_ids = _real_visits(sched)
    live = sum(sizes)
    offsets = np.asarray(sched.offsets)
    assert offsets.shape == (groups + 1,) and offsets[-1] == live
    assert groups <= visits <= -(-live // tile) + groups
    assert sched.group_ids.shape == (rows // tile + groups,)
    assert (np.asarray(sched.group_ids) < groups).all()
    assert (np.asarray(sched.tile_ids) < rows // tile).all()
    for g, size in enumerate(sizes):
        mine = tile_ids[group_ids == g]
        assert len(mine) == len(set(mine)) >= 1  # an empty group keeps one
        if size:
            held = np.arange(offsets[g], offsets[g + 1])
            assert sorted(mine) == sorted(set(held // tile))
    assert (np.diff(tile_ids) >= 0).all() and (np.diff(group_ids) >= 0).all()
    # no real visit of a tile that holds no live row, but an empty group's
    assert all(t * tile < max(live, 1) or sizes[g] == 0
               for g, t in zip(group_ids, tile_ids))
    assert (np.asarray(sched.group_ids)[visits:] == group_ids[-1]).all()
    assert (np.asarray(sched.tile_ids)[visits:] == tile_ids[-1]).all()


@pytest.mark.parametrize("gated,kernels", [
    (True, ["moe_gmm", "moe_gmm_dswiglu", "moe_gmm_pair", "moe_gmm_swiglu",
            "moe_tgmm", "moe_tgmm", "moe_tgmm"]),
    (False, ["moe_gmm", "moe_gmm", "moe_gmm_drelu2", "moe_gmm_relu2",
             "moe_tgmm", "moe_tgmm"])], ids=["swiglu", "relu2"])
def test_no_pass_over_the_rows_between_the_expert_kernels(gated, kernels):
    """The lowered layer's gradient: under scope ``moe_experts`` every
    array of a row a (token, choice) is made and read by a Pallas kernel
    alone — no ``add_any`` of two cotangents of the rows, no activation
    (SwiGLU's ``logistic`` and ``mul``, the ungated expert's ``max`` and
    square) or its derivative as an XLA pass over the static buffer — and
    the rule's kernels are there by name: seven, or six without a gate."""
    args = layer_inputs()
    if not gated:
        args = args[:3] + (None,) + args[4:]
    tile = 16
    rows = -(-T * K // tile) * tile
    fn = jax.grad(lambda *a: jnp.sum(moe.moe_block(
        *a, num_selected=K, tile=tile)[0] ** 2),
        argnums=[i for i in range(6) if args[i] is not None])

    def outside_kernels(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from outside_kernels(sub)

    under = [e for e in outside_kernels(jax.make_jaxpr(fn)(*args).jaxpr)
             if "moe_experts" in str(e.source_info.name_stack)]
    assert sorted(e.params["name"] for e in under
                  if e.primitive.name == "pallas_call") == kernels
    for eqn in under:
        name = eqn.primitive.name
        assert name not in ("add_any", "logistic", "max"), eqn
        over_rows = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                     if getattr(v.aval, "shape", ())[:1] == (rows,)]
        assert name == "pallas_call" or not over_rows, eqn


@pytest.mark.parametrize("first,held", [(0, E), (0, 2), (5, 3)],
                         ids=["every_expert", "first2", "last3"])
def test_rows_visited_share_reads_what_the_schedule_says(first, held):
    """The counter is the schedule's own: real visits x tile over the
    buffer's rows — about 1 + groups x tile / rows where every expert is
    held, about the held share where few are."""
    args = layer_inputs()
    tile, rows = 16, T * K
    _, stats = moe.moe_block(
        *args[:3], *(w[first:first + held] for w in args[3:]),
        num_selected=K, tile=tile, first_expert=first)
    sizes = np.bincount(np.asarray(seeded_experts()).reshape(-1),
                        minlength=E)[first:first + held]
    sched = moe.make_schedule(jnp.asarray(sizes), rows, tile)
    want = int(sched.num_visits[0]) * tile / rows
    assert float(stats["rows_visited_share"]) == pytest.approx(want)
    live = sizes.sum()
    assert live / rows <= want <= (live + (held + 1) * tile) / rows
    assert (want > 1.0) == (held == E)


def _routing(case):
    """(experts (T, k), row tile, ep ranks, this rank) of a named case."""
    seeded = seeded_experts()
    if case == "same_experts":
        return jnp.broadcast_to(jnp.arange(K), (T, K)), 16, 1, 0
    if case == "empty_expert":  # 5's choices go to the next expert
        return jnp.where(seeded == 5, (seeded + 1) % E, seeded), 16, 1, 0
    if case == "ragged_tile":   # 288 rows in 384
        return seeded, 128, 1, 0
    if case.startswith("ep"):
        ep, rank = (int(part.strip("eprank")) for part in case.split("_"))
        return seeded, 16, ep, rank
    return seeded, 16, 1, 0


@pytest.mark.parametrize("case", [
    "seeded", "same_experts", "empty_expert", "ragged_tile", "ep2_rank0",
    "ep2_rank1", "ep4_rank0", "ep4_rank1", "ep4_rank2", "ep4_rank3"])
def test_row_index_is_in_range_and_its_own_inverse(case):
    """What the gathers promise (``mode="promise_in_bounds"``): every
    index ``_row_index`` builds is in range, ``slot_row[t, j]`` is the
    row whose ``row_slot`` is ``t * k + j``, the rows lie group by group
    with their gates, and what is past the assignments lies past the
    groups' sum."""
    experts, tile, ep, rank = _routing(case)
    local, n = E // ep, T * K
    flat = (experts.reshape(-1) - rank * local) % E  # as moe_block's ranks
    sizes = np.bincount(np.asarray(flat), minlength=E)[:local]
    if case == "empty_expert":
        assert sizes[5] == 0
    rows = -(-n // tile) * tile
    gates = jax.random.uniform(jax.random.PRNGKey(1), (T, K), minval=0.1)
    row_token, row_slot, slot_row, row_gate = (
        np.asarray(a) for a in moe._row_index(flat, gates, rows))
    assert row_token.shape == row_slot.shape == (rows,)
    assert slot_row.shape == (T, K)
    assert (0 <= row_slot).all() and (row_slot < n).all()
    assert (0 <= row_token).all() and (row_token < T).all()
    assert (0 <= slot_row).all() and (slot_row < n).all()
    assert (row_token == row_slot // K).all()
    assert (row_slot[slot_row.reshape(-1)] == np.arange(n)).all()
    assert (slot_row.reshape(-1)[row_slot[:n]] == np.arange(n)).all()
    groups = np.asarray(flat)[row_slot[:n]]
    assert (np.diff(groups) >= 0).all()
    offsets = np.asarray(moe.make_schedule(jnp.asarray(sizes), rows,
                                           tile).offsets)
    for g in range(local):
        assert (groups[offsets[g]:offsets[g + 1]] == g).all()
    # other ranks' rows and the padding: past the schedule's end
    assert offsets[-1] == offsets[local] == sizes.sum() <= n <= rows
    assert (groups[offsets[local]:] >= local).all()
    assert (row_slot[n:] == 0).all()
    # each row's gate rode along in the sort; the padding's is 0
    assert (row_gate[:n] == np.asarray(gates).reshape(-1)[row_slot[:n]]).all()
    assert row_gate.shape == (rows,) and (row_gate[n:] == 0).all()


def _plain_formulation():
    """The four gathers as they were before they promised anything or
    stopped anywhere (``jnp.take``'s fill mode over EVERY row of the
    buffer, scalars gathered one by one; of a share, the rows that are
    not live selected away where they are read): what the layer must
    still compute, to the bit."""
    take = functools.partial(jnp.take, axis=0)

    def live_only(picked, row, live):
        if live is None:
            return picked
        return jnp.where((row < live).reshape(row.shape + (1,) * (
            picked.ndim - row.ndim)), picked, 0.0)

    @jax.custom_vjp
    def dispatch(x, row_token, slot_row, live):
        return take(x, row_token)

    def dispatch_bwd(res, d_rows):
        slot_row, live = res
        picked = live_only(take(d_rows, slot_row), slot_row, live)
        d_x = jnp.sum(picked.astype(jnp.float32), axis=1)
        return d_x.astype(d_rows.dtype), None, None, None

    dispatch.defvjp(lambda x, rt, sr, live: (dispatch(x, rt, sr, live),
                                             (sr, live)), dispatch_bwd)

    @jax.custom_vjp
    def combine(y_rows, gates, row_token, row_slot, slot_row, row_gate,
                live):
        picked = live_only(take(y_rows, slot_row), slot_row, live)
        return jnp.einsum("tk,tkd->td", gates,
                          picked.astype(jnp.float32)).astype(y_rows.dtype)

    def combine_bwd(res, d_out):
        y_rows, gates, row_token, row_slot, slot_row, _, live = res
        d_out_rows = take(d_out, row_token).astype(jnp.float32)
        row_gate = jnp.take(gates.reshape(-1), row_slot)
        d_rows = (d_out_rows * row_gate[:, None]).astype(y_rows.dtype)
        d_row_gate = live_only(
            jnp.sum(y_rows.astype(jnp.float32) * d_out_rows, -1),
            jnp.arange(row_token.shape[0]), live)
        d_gates = jnp.take(d_row_gate, slot_row)
        return (d_rows, d_gates.astype(gates.dtype)) + (None,) * 5

    combine.defvjp(lambda *a: (combine(*a), a), combine_bwd)
    return dispatch, combine


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _layer_and_grads(ep):
    """jit(value and gradients of the layer), on one device or with the
    experts over ``ep`` devices."""
    layer = functools.partial(moe.moe_block, num_selected=K, tile=16)
    if ep > 1:
        mesh = jax.make_mesh((ep,), ("ep",), devices=jax.devices()[:ep])
        rank = P("ep")
        layer = jax.shard_map(
            functools.partial(layer, expert_axis="ep", sum_axes=("ep",)),
            mesh=mesh, in_specs=(P(), P(), P(), rank, rank, rank),
            out_specs=(P(), P()), check_vma=False)
    return jax.value_and_grad(lambda *a: jnp.sum(layer(*a)[0] ** 2),
                              argnums=range(6))


@pytest.mark.parametrize("ep", [1, 2], ids=["one_device", "ep2"])
def test_gathers_promise_their_indices_and_equal_the_plain_ones(
        ep, monkeypatch):
    """No row gather of the layer's gradient program may be out of range
    and be filled (``FILL_OR_DROP``: a pass over ``(M, d)`` behind each
    on the chip), and nothing it computes moves: no sum is reordered, so
    output and gradients equal the plain formulation's bit for bit (of a
    share, whose token-side sums add in their own order, to the last
    bits)."""
    args = layer_inputs()
    mode = jax.lax.GatherScatterMode

    def row_gather_modes(fn):
        return [e.params["mode"]
                for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
                if e.primitive.name == "gather"
                and e.invars[0].aval.shape[1:] == (D,)]

    fn = _layer_and_grads(ep)
    # dispatch, combine, and their gradients; of a share (an ``ep`` rank)
    # each token-side sum is two: the live rows, then the runs' ends
    assert row_gather_modes(fn) == [mode.PROMISE_IN_BOUNDS] * (
        4 if ep == 1 else 6)
    got = jax.jit(fn)(*args)
    dispatch, combine = _plain_formulation()
    monkeypatch.setattr(moe, "_dispatch", dispatch)
    monkeypatch.setattr(moe, "_combine", combine)
    fn = _layer_and_grads(ep)
    assert row_gather_modes(fn) == [mode.FILL_OR_DROP] * 4
    want = jax.jit(fn)(*args)
    if ep > 1:
        # a share adds a token's live rows oldest first, where XLA's
        # reduction over all k picks its own order: the last bits
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
        for name, g, w in zip(NAMES, got[1], want[1]):
            assert float(jnp.abs(g - w).max()) <= 4e-6 * float(
                jnp.abs(w).max()), name
        return
    assert float(got[0]) == float(want[0])
    for name, g, w in zip(NAMES, got[1], want[1]):
        assert bool((g == w).all()), name


def test_auxiliary_losses_against_their_formulas():
    x, norm, router = layer_inputs(seed=3, router_scale=1.5)[:3]
    _, stats = moe.moe_block(*layer_inputs(seed=3, router_scale=1.5),
                             num_selected=K, tile=16)
    h = np.asarray(x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm, np.float64)
    logits = h @ np.asarray(router, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, axis=-1, kind="stable")[:, :K]
    counts = np.bincount(chosen.reshape(-1), minlength=E)  # all K choices
    balance = E * np.sum(counts / T * p.mean(0))
    lse = np.log(np.exp(logits).sum(-1))
    assert float(stats["aux_loss"]) == pytest.approx(balance, rel=1e-5)
    assert float(stats["z_loss"]) == pytest.approx(np.mean(lse ** 2),
                                                   rel=1e-5)
    assert float(stats["load_max_over_mean"]) == pytest.approx(
        counts.max() / (T * K / E), rel=1e-6)
    first_choice_only = E * np.sum(
        np.bincount(chosen[:, 0], minlength=E) / T * p.mean(0))
    assert abs(balance - first_choice_only) > 0.5
