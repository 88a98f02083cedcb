"""The dropless expert layer (``ops/moe.py``) and an OLMoE-shaped model
through ``loss_fn`` against the benchmark's plain reference.  CPU, float32
unless said; the Pallas kernels run in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import olmoe
from ray_tpu.models import LlamaConfig, init_params, loss_fn, \
    param_logical_axes
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshConfig, make_mesh, shard_pytree, use_mesh

T, D, E, K, M = 96, 32, 8, 3, 48
NAMES = ("x", "norm", "router", "w_gate", "w_up", "w_down")


def _layer_inputs(seed=0, router_scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (T, D)),
            1.0 + 0.3 * jax.random.normal(ks[5], (D,)),
            jax.random.normal(ks[1], (D, E)) * router_scale,
            jax.random.normal(ks[2], (E, D, M)) * 0.2,
            jax.random.normal(ks[3], (E, D, M)) * 0.2,
            jax.random.normal(ks[4], (E, M, D)) * 0.2)


def _per_token_loop(x, norm, router, w_gate, w_up, w_down, k=K, first=0):
    """Every token through each of its k experts, one choice at a time;
    of the chip that holds the ``w_gate.shape[0]`` experts from ``first``
    on, a choice of an absent expert adds nothing."""
    held = w_gate.shape[0]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm
    gates, experts = jax.lax.top_k(jax.nn.softmax(h @ router, -1), k)
    out = x
    for j in range(k):
        mine = (experts[:, j] >= first) & (experts[:, j] < first + held)
        e = jnp.clip(experts[:, j] - first, 0, held - 1)
        a = jnp.einsum("td,tdm->tm", h, w_gate[e])
        b = jnp.einsum("td,tdm->tm", h, w_up[e])
        y = jnp.einsum("tm,tmd->td", jax.nn.silu(a) * b, w_down[e])
        out = out + jnp.where(mine[:, None], gates[:, j:j + 1] * y, 0.0)
    return out


def _assert_gradients_equal(layer, loop, args):
    """Every gradient of ``sum(layer(...) ** 2)`` against the loop's."""
    got = jax.grad(lambda *a: jnp.sum(layer(*a) ** 2),
                   argnums=range(6))(*args)
    ref = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2),
                   argnums=range(6))(*args)
    for name, g, r in zip(NAMES, got, ref):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - r).max()) < 1e-6 * float(
            jnp.abs(r).max()) + 1e-6, name
    return got


@pytest.mark.parametrize("tile", [16, 128], ids=["tile16", "tile128"])
def test_layer_equals_a_per_token_loop(tile):
    """Output and the gradients to x, the norm, the router and all three
    expert tensors; 288 rows in 8 uneven groups, no multiple of a tile."""
    args = _layer_inputs()
    layer = functools.partial(moe.moe_block, num_selected=K, tile=tile)
    out, stats = layer(*args)
    want = _per_token_loop(*args)
    assert float(jnp.abs(out - want).max()) < 5e-6
    assert float(stats["dropped"]) == 0
    _assert_gradients_equal(lambda *a: layer(*a)[0], _per_token_loop, args)


def test_group_sizes_sum_to_all_assignments_and_fit_no_tile():
    sizes = np.bincount(np.asarray(_seeded_experts()).reshape(-1),
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
    x, norm, router, w_gate, w_up, w_down = _layer_inputs()
    x = jnp.abs(x)  # so that its product with the first K columns is > 0
    router = jnp.zeros((D, E)).at[:, :K].set(1.0)
    args = (x, jnp.ones_like(norm), router, w_gate, w_up, w_down)
    out, stats = moe.moe_block(*args, num_selected=K, tile=16)
    assert float(stats["dropped"]) == 0
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / K)
    assert float(jnp.abs(out - _per_token_loop(*args)).max()) < 5e-6
    grads = jax.grad(lambda *a: jnp.sum(
        moe.moe_block(*a, num_selected=K, tile=16)[0] ** 2),
        argnums=range(6))(*args)
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


def _poisoned(monkeypatch):
    """Every buffer the share's path allocates or a kernel leaves
    unvisited holds NaN past the live rows BEFORE anyone reads it: the
    row buffers under the two loops, and every output of the grouped
    kernels, the FFN's residuals among them (interpret mode hands out NaN
    there already; said again, so the test does not rest on it).  ``_live_token_sum`` keeps its traces: none
    from before the poison may serve."""
    jax.clear_caches()
    monkeypatch.setattr(
        moe, "_row_buffer", lambda shape, dtype: jnp.full(shape, jnp.nan,
                                                          dtype))
    call = moe._gmm_call

    def call_with_poisoned_tails(form, operands, sched, *rest, **kw):
        row = jnp.arange(operands[0].shape[0])[:, None]
        return [jnp.where(row < sched.offsets[-1], out, jnp.nan)
                for out in call(form, operands, sched, *rest, **kw)]

    monkeypatch.setattr(moe, "_gmm_call", call_with_poisoned_tails)


@pytest.mark.parametrize("first,held,tile", [
    (0, 2, 16), (2, 3, 16), (5, 3, 128), (6, 2, 16), (0, 1, None)],
    ids=["first2", "middle3", "last3_tile128", "last2", "one_default_tile"])
@pytest.mark.parametrize("chunk", [1024, 40], ids=["one_chunk", "chunks"])
def test_share_equals_the_per_token_loop_on_poisoned_tails(
        first, held, tile, chunk, monkeypatch):
    """A chip's share of the layer (``first_expert``, ``E' < E``): value
    and EVERY gradient against the per-token loop, with the buffers'
    tails poisoned: nothing may read a row past the live ones unmasked.
    In chunks of 40 the loops over the live rows make several trips and
    a token's run of live choices crosses them."""
    _poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    args = _layer_inputs()
    cut = lambda a: tuple(a[:3]) + tuple(w[first:first + held]
                                         for w in a[3:])
    layer = lambda *a: moe.moe_block(*cut(a), num_selected=K, tile=tile,
                                     first_expert=first)
    loop = lambda *a: _per_token_loop(*cut(a), first=first)
    out, stats = layer(*args)
    assert float(jnp.abs(out - loop(*args)).max()) < 5e-6
    assert float(stats["dropped"]) == 0
    assert 0 < float(stats["held_share"]) < 1
    got = _assert_gradients_equal(lambda *a: layer(*a)[0], loop, args)
    for g in got[3:]:  # the absent experts' tensors got no gradient
        assert float(jnp.abs(g[:first]).max(initial=0)) == 0
        assert float(jnp.abs(g[first + held:]).max(initial=0)) == 0


@pytest.mark.parametrize("chunk", [1024, 64], ids=["one_chunk", "chunks"])
def test_share_that_every_token_chooses_is_exact_at_full_buffer(
        chunk, monkeypatch):
    """There is no capacity: when every token chooses only held experts
    the live rows are ALL ``T * k`` of the buffer, the loops run to its
    end, nothing is dropped and value and gradients are the loop's."""
    _poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    x, norm, router, w_gate, w_up, w_down = _layer_inputs()
    x = jnp.abs(x)
    router = jnp.zeros((D, E)).at[:, 2:2 + K].set(
        1.0 + 0.1 * jnp.arange(K))       # everyone to experts 2, 3, 4
    args = (x, jnp.ones_like(norm), router, w_gate, w_up, w_down)
    cut = lambda a: tuple(a[:3]) + tuple(w[2:6] for w in a[3:])
    layer = lambda *a: moe.moe_block(*cut(a), num_selected=K, tile=16,
                                     first_expert=2)
    loop = lambda *a: _per_token_loop(*cut(a), first=2)
    out, stats = layer(*args)
    assert float(stats["held_share"]) == 1.0
    assert float(stats["dropped"]) == 0
    assert float(jnp.abs(out - loop(*args)).max()) < 5e-6
    _assert_gradients_equal(lambda *a: layer(*a)[0], loop, args)


FFN_ROWS = 256
FFN_CASES = {  # group sizes over FFN_ROWS rows
    "every_group_held": (128, 128),
    "share_poisoned_past_live": (40, 30, 50, 20),
    "empty_group": (90, 0, 100, 66),
    "tile_two_groups_share": (100, 60, 50, 46),
    "every_row_in_one_group": (0, 256, 0),
}


def _composition(x, w_gate, w_up, w_down, sched, tile):
    """What ``expert_ffn`` replaces: three grouped products, SwiGLU
    between them in plain XLA over the whole buffer — or, without a gate
    (``w_gate`` None), two and the square of the positive part."""
    from ray_tpu.ops.layers import swiglu

    product = functools.partial(moe.grouped_matmul, sched=sched, tile=tile,
                                interpret=True)
    if w_gate is None:
        return product(jnp.square(jax.nn.relu(product(x, w_up))), w_down)
    return product(swiglu(product(x, w_gate), product(x, w_up)), w_down)


@pytest.mark.parametrize("tile", [16, 128], ids=["tile16", "tile128"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(FFN_CASES))
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_expert_ffn_equals_the_composition_it_replaces(gated, case, dtype,
                                                       tile):
    """Value and all gradients (four; three of the expert without a gate)
    of the one rule against ``grouped_matmul``s with the activation
    between them, on the live rows (past them both are unspecified; of the
    share, rows and cotangent hold NaN there, so a kernel that read one
    unmasked would spread it into a weight's gradient).  In bfloat16 the
    rule rounds the activation once, from float32: it lies no further from
    the float32 composition than today's does."""
    sizes = FFN_CASES[case]
    live, groups, d, m = sum(sizes), len(sizes), 32, 48
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    f32 = jnp.float32
    past = (jnp.arange(FFN_ROWS) >= live)[:, None]
    x, d_y = (jnp.where(past, jnp.nan, jax.random.normal(k, (FFN_ROWS, d)))
              for k in ks[:2])
    weights = tuple(jax.random.normal(k, shape) * 0.3 for k, shape in zip(
        ks[2:], [(groups, d, m), (groups, d, m), (groups, m, d)]))
    sched = moe.make_schedule(jnp.asarray(sizes), FFN_ROWS, tile)

    def value_and_grads(fn, dtype):
        args = tuple(a.astype(dtype) for a in (x,) + weights[not gated:])
        gate = () if gated else (None,)
        y, vjp = jax.vjp(lambda x, *w: fn(x, *gate, *w, sched, tile), *args)
        d_x, *d_w = vjp(d_y.astype(dtype))
        return [a.astype(f32) for a in (y[:live], d_x[:live], *d_w)]

    fused = functools.partial(moe.expert_ffn, interpret=True)
    got = value_and_grads(fused, dtype)
    want = value_and_grads(_composition, f32)
    names = ("y", "d_x", "d_w_gate", "d_w_up", "d_w_down")
    names = names if gated else names[:2] + names[3:]
    assert len(got) == len(want) == len(names)
    if dtype == f32:
        for name, g, w in zip(names, got, want):
            assert bool(jnp.isfinite(g).all()), name
            assert float(jnp.abs(g - w).max()) <= 2e-6 * float(
                jnp.abs(w).max()), name
        return
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    today = value_and_grads(_composition, dtype)
    for name, g, t, w in zip(names, got, today, want):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - w).max()) <= 3e-2 * float(
            jnp.abs(w).max()), name
        assert rms(g - w) <= 1.02 * rms(t - w), name


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
    args = _layer_inputs()
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


def _token_sum_inputs(live, dtype, tokens=41, k=4, d=24, seed=0):
    """``rows (n, d)`` with NaN from ``live`` on, ``slot_row (tokens, k)``
    a permutation of the rows in which token 0 has ``k`` live choices,
    token 1 one and token 2 none (as far as ``live`` allows), the others
    what the seed deals them, and float32 ``weights``."""
    n = tokens * k
    rng = np.random.default_rng(seed)
    alive, dead = (list(rng.permutation(np.arange(lo, hi)))
                   for lo, hi in ((0, live), (live, n)))
    slot_row = np.full((tokens, k), -1)

    def deal(token, pile, count):
        count = min(count, len(pile))
        free = np.flatnonzero(slot_row[token] < 0)
        for j in rng.permutation(free)[:count]:
            slot_row[token, j] = pile.pop()

    deal(0, alive, k)
    deal(1, alive, 1), deal(1, dead, k - 1)
    deal(2, dead, k)
    rest = list(rng.permutation(alive + dead))
    for token in range(tokens):
        deal(token, rest, k)
    assert sorted(slot_row.reshape(-1)) == list(range(n))
    rows = rng.standard_normal((n, d)).astype(np.float32)
    rows[live:] = np.nan
    weights = rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(slot_row, jnp.int32),
            jnp.asarray(weights))


@pytest.mark.parametrize("chunk", [16, 1024], ids=["chunks", "one_chunk"])
@pytest.mark.parametrize("weighted", [True, False], ids=["gates", "ones"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", [0, 20, 82, 164],
                         ids=["none", "eighth", "half", "all"])
def test_token_sum_equals_a_loop_over_the_live_choices(
        live, dtype, weighted, chunk, monkeypatch):
    """The one token-side sum of a share (``_combine``'s forward with the
    gates, ``_dispatch``'s gradient without) against a loop over each
    token's choices, with every row from ``live`` on AND the buffer of
    the runs poisoned: no live rows, an eighth, half and all ``T * k`` of
    them; tokens with 0, 1 and ``k`` live choices; runs that cross the
    chunks of 16 (164 slots: the last trip runs over the one before it);
    24 columns."""
    _poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    rows, slot_row, weights = _token_sum_inputs(live, dtype)
    got = moe._token_sum(rows, slot_row, weights if weighted else None,
                         jnp.int32(live))
    assert got.dtype == dtype and got.shape == (41, 24)
    want = np.zeros((41, 24), np.float32)
    for t, choices in enumerate(np.asarray(slot_row)):
        for j, row in enumerate(choices):
            if row < live:
                want[t] += np.asarray(rows[row], np.float32) * (
                    np.float32(weights[t, j]) if weighted else 1.0)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    if live < 164:  # a token without a live choice reads exact zeros
        assert (got[2] == 0).all()
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


@pytest.mark.parametrize("chunk", [1024, 32], ids=["one_chunk", "chunks"])
@pytest.mark.parametrize("first,held", [(0, E), (0, 2), (5, 3)],
                         ids=["every_expert", "first2", "last3"])
def test_token_rows_read_share_reads_what_the_index_says(
        first, held, chunk, monkeypatch):
    """The counter is the code's own: a token-side sum of a share fetches
    ``chunk + k - 1`` rows a trip over the live rows and ``T`` at the
    runs' ends; where every expert is held it is the one gather of a row
    a (token, choice): 1."""
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    args = _layer_inputs()
    _, stats = moe.moe_block(
        *args[:3], *(w[first:first + held] for w in args[3:]),
        num_selected=K, tile=16, first_expert=first)
    live = int(np.bincount(np.asarray(_seeded_experts()).reshape(-1),
                           minlength=E)[first:first + held].sum())
    trip = min(chunk, T * K)
    want = 1.0 if held == E else (
        -(-live // trip) * (trip + K - 1) + T) / (T * K)
    assert float(stats["token_rows_read_share"]) == pytest.approx(want)
    assert float(stats["held_share"]) == pytest.approx(live / (T * K))


@pytest.mark.parametrize("first,held", [(0, E), (0, 2), (5, 3)],
                         ids=["every_expert", "first2", "last3"])
def test_rows_visited_share_reads_what_the_schedule_says(first, held):
    """The counter is the schedule's own: real visits x tile over the
    buffer's rows — about 1 + groups x tile / rows where every expert is
    held, about the held share where few are."""
    args = _layer_inputs()
    tile, rows = 16, T * K
    _, stats = moe.moe_block(
        *args[:3], *(w[first:first + held] for w in args[3:]),
        num_selected=K, tile=tile, first_expert=first)
    sizes = np.bincount(np.asarray(_seeded_experts()).reshape(-1),
                        minlength=E)[first:first + held]
    sched = moe.make_schedule(jnp.asarray(sizes), rows, tile)
    want = int(sched.num_visits[0]) * tile / rows
    assert float(stats["rows_visited_share"]) == pytest.approx(want)
    live = sizes.sum()
    assert live / rows <= want <= (live + (held + 1) * tile) / rows
    assert (want > 1.0) == (held == E)


def _seeded_experts():
    x, norm, router = _layer_inputs()[:3]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm
    return jax.lax.top_k(jax.nn.softmax(h @ router, -1), K)[1]


def _routing(case):
    """(experts (T, k), row tile, ep ranks, this rank) of a named case."""
    seeded = _seeded_experts()
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
    args = _layer_inputs()
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
    x, norm, router = _layer_inputs(seed=3, router_scale=1.5)[:3]
    _, stats = moe.moe_block(*_layer_inputs(seed=3, router_scale=1.5),
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


# ------------------------------------------- a tiny OLMoE through loss_fn --

CONF = dict(num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, rope_theta=10000.0, rms_norm_eps=1e-5,
            num_experts_per_tok=3, norm_topk_prob=False, qk_norm=True,
            router_aux_loss_coef=0.01, router_z_loss_coef=0.001)


def _tiny_olmoe(**kw):
    fields = dict(num_experts=8, num_selected=3, qk_norm=True, norm_eps=1e-5,
                  aux_loss_coef=0.01, z_loss_coef=0.001, attn_impl="flash")
    fields.update(kw)
    return LlamaConfig.tiny(**fields)


def _params_and_tokens(cfg, rows=2, seq=64):
    params = init_params(jax.random.PRNGKey(0), cfg)
    # norms away from their initial ones, so that leaving one out shows
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    params["layers"] = {
        name: (a * jax.random.uniform(next(keys), a.shape, jnp.float32,
                                      0.5, 1.5).astype(a.dtype)
               if name.endswith("norm") else a)
        for name, a in params["layers"].items()}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, seq + 1), 0,
                                cfg.vocab_size)
    return params, tokens


def test_tiny_olmoe_equals_the_plain_reference():
    cfg = _tiny_olmoe()
    params, tokens = _params_and_tokens(cfg)
    (total, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg), has_aux=True)(params)
    want = olmoe.loss_parts(params, tokens, CONF)
    assert float(total) == pytest.approx(float(want["total"]), rel=2e-6)
    for part in ("loss", "aux_loss", "z_loss"):
        assert float(metrics[part]) == pytest.approx(float(want[part]),
                                                     rel=2e-6), part
    assert float(metrics["moe_dropped"]) == 0
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 8 / 3
    ref = jax.grad(lambda p: olmoe.loss(p, tokens, CONF))(params)
    worst = jax.tree.map(
        lambda g, r: float(jnp.abs(g - r).max() / jnp.abs(r).max()),
        grads, ref)
    assert max(jax.tree.leaves(worst)) < 1e-5, worst


@pytest.mark.parametrize("left_out", ["qk_norm", "z_loss", "one_expert",
                                      "aux_loss", "renormalised"])
def test_reference_check_fails_when_part_of_the_layer_is_left_out(left_out):
    """What the benchmark's check (relative ``LOSS_RTOL``) must catch."""
    broken = {"qk_norm": dict(qk_norm=False),
              "z_loss": dict(z_loss_coef=0.0),
              "aux_loss": dict(aux_loss_coef=0.0),
              "one_expert": dict(num_selected=2),
              "renormalised": dict(norm_topk_prob=True)}[left_out]
    cfg = _tiny_olmoe()
    params, tokens = _params_and_tokens(cfg)
    want = float(olmoe.loss(params, tokens, CONF))
    got = float(loss_fn(params, {"tokens": tokens}, _tiny_olmoe(**broken))[0])
    assert abs(got - want) > 10 * olmoe.LOSS_RTOL * want, (got, want)


def test_bfloat16_inside_the_stated_tolerance():
    """bfloat16 parameters and activations against the float32 reference
    on the same (bfloat16) parameters, 2048 tokens."""
    cfg = _tiny_olmoe(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                      max_seq_len=512)
    params, tokens = _params_and_tokens(cfg, rows=4, seq=512)
    got = float(loss_fn(params, {"tokens": tokens}, cfg)[0])
    want = float(olmoe.loss(params, tokens, CONF))
    assert abs(got - want) <= olmoe.loss_rtol(4 * 512) * want, (got, want)


@pytest.mark.parametrize("mesh_kw", [dict(ep=2), dict(ep=4), dict(dp=2, ep=2,
                                                                  tp=2)],
                         ids=["ep2", "ep4", "dp2_ep2_tp2"])
def test_expert_parallel_equals_one_device(mesh_kw):
    cfg = _tiny_olmoe()
    params, tokens = _params_and_tokens(cfg, rows=4)
    loss = lambda p, t, mesh=None: loss_fn(p, {"tokens": t}, cfg, mesh=mesh)
    (want, m1), g1 = jax.value_and_grad(loss, has_aux=True)(params, tokens)
    n = int(np.prod(list(mesh_kw.values())))
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:n])
    with use_mesh(mesh):
        sharded = shard_pytree(params, param_logical_axes(cfg), mesh)
        toks = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        (got, m2), g2 = jax.jit(jax.value_and_grad(
            functools.partial(loss, mesh=mesh), has_aux=True))(sharded, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("aux_loss", "z_loss", "moe_load_max_over_mean"):
        assert float(m2[name]) == pytest.approx(float(m1[name]), rel=1e-5)
    assert float(m2["moe_dropped"]) == 0
    # an ``ep`` rank is a share and takes the token-side sum over its live
    # rows (one trip over a shard's t * 3 slots, and t rows at the ends);
    # one device holds every expert and gathers a row a (token, choice)
    t = 4 * 64 // mesh_kw.get("dp", 1)
    assert float(m1["moe_token_rows_read_share"]) == 1.0
    assert float(m2["moe_token_rows_read_share"]) == pytest.approx(
        (t * 3 + 2 + t) / (t * 3))
    worst = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
        jax.device_get(g2), g1)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst


@pytest.mark.parametrize("ep", [2, 4], ids=["ep2", "ep4"])
def test_a_share_over_ep_equals_one_device(ep):
    """A JoyAI-LLM-Flash-shaped model (latent attention, a leading dense
    layer, sigmoid top-4 of 16 with a selection bias, a shared expert, a
    predicted-ahead module) of which this host holds 8 experts, on
    ``MeshConfig(ep=ep)`` with its tokens split over the ranks and the
    exchange between them: the loss, every gradient, the experts' counts
    and the selection bias a train step moves are the one-device
    program's."""
    from ray_tpu.models.llama import loss_and_counts
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    cfg = LlamaConfig.tiny(
        num_layers=3, leading_dense=1, dense_mlp_dim=96, mlp_dim=32,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, num_experts=16, num_selected=4, experts_held=8,
        norm_topk_prob=True, shared_experts=1, router_scoring="sigmoid",
        topk_method="noaux_tc", routed_scaling_factor=2.5, num_nextn=1,
        aux_loss_coef=0.0, attn_impl="flash", remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                cfg.vocab_size)
    loss = lambda p, t, mesh=None: loss_and_counts(
        p, {"tokens": t}, cfg, mesh=mesh)
    (want, (m1, c1)), g1 = jax.value_and_grad(loss, has_aux=True)(
        params, tokens)
    mesh = make_mesh(MeshConfig(ep=ep), devices=jax.devices()[:ep])
    rows = named_sharding(mesh, "batch", None)
    assert rows.spec == P(("dp", "fsdp", "ep"), None)
    with use_mesh(mesh):
        sharded = shard_pytree(params, param_logical_axes(cfg), mesh)
        assert sharded["layers"][1]["w_gate"].sharding.spec[1] == "ep"
        toks = jax.device_put(tokens, rows)
        (got, (m2, c2)), g2 = jax.jit(jax.value_and_grad(
            functools.partial(loss, mesh=mesh), has_aux=True))(sharded, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("loss", "mtp_loss", "moe_held_share",
                 "moe_load_max_over_mean"):
        assert float(m2[name]) == pytest.approx(float(m1[name]), rel=1e-5)
    assert float(m2["moe_dropped"]) == 0
    assert float(m1["moe_rank_rows_max_over_mean"]) == 1.0
    assert 1.0 <= float(m2["moe_rank_rows_max_over_mean"]) <= ep
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_array_equal(a, b)     # over ALL the token shards
    worst = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
        jax.device_get(g2), g1)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst
    # one train step through the normal path: the bias moves by the host's
    # counts, the same way on every rank
    opt = default_optimizer()
    alone, _ = make_train_step(cfg, opt, donate=False)(
        init_train_state(jax.random.PRNGKey(0), cfg, opt), {"tokens": tokens})
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh=mesh)
    spread, metrics = make_train_step(cfg, opt, mesh=mesh, donate=False)(
        state, {"tokens": toks})
    for a, b in ((alone.params["layers"][1], spread.params["layers"][1]),
                 (alone.params["mtp"]["layers"],
                  spread.params["mtp"]["layers"])):
        np.testing.assert_array_equal(a["router_bias"], b["router_bias"])
    assert np.isfinite(float(metrics["grad_norm"]))
