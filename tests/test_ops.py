"""Numerics tests for ops/ kernels vs the XLA reference implementation.

Pattern follows the reference's per-component unit suites (SURVEY.md §4):
every kernel is tested against an oracle, fwd and bwd, causal and not.
Pallas kernels run in interpret mode on the CPU backend — same code path
that compiles for TPU.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import (
    flash_attention, mha_reference, ring_attention, ulysses_attention,
    rms_norm, rope, apply_rope,
)
from ray_tpu.ops import attention, rotary
from ray_tpu.ops.attention import causal_tile_counts, choose_tiles
from ray_tpu.ops.layers import scaled_rope
from ray_tpu.ops.moe import moe_block
from ray_tpu.parallel import MeshConfig, make_mesh, use_mesh

B, S, H, D = 2, 128, 4, 32


@pytest.fixture(scope="module")
def qkv():
    key = jax.random.PRNGKey(0)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32)
                 for k in jax.random.split(key, 3))


def _qkv(b, sq, sk, h, h_kv, d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(sq + sk + h_kv + d), 3)
    return (jax.random.normal(keys[0], (b, sq, h, d), jnp.float32).astype(dtype),
            jax.random.normal(keys[1], (b, sk, h_kv, d), jnp.float32).astype(dtype),
            jax.random.normal(keys[2], (b, sk, h_kv, d), jnp.float32).astype(dtype))


def _value_and_grads(fn, loss, *args):
    """``fn(*args)`` and the gradients of ``loss(fn(*args))`` to every
    argument, as ONE compiled program."""
    def scalar(*a):
        out = fn(*a)
        return loss(out), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def _sum_of_squares(out):
    return (out.astype(jnp.float32) ** 2).sum()


@pytest.fixture(scope="module")
def causal_reference(qkv):
    """``mha_reference`` on the module's q, k, v and its gradients: once
    for every tile schedule."""
    return _value_and_grads(lambda *a: mha_reference(*a, causal=True),
                            _sum_of_squares, *qkv)


# (b, sq, sk, h, h_kv, d, dtype, causal, block caps) -> the tiles
# ``choose_tiles`` picks and the kinds of tile they produce.
FLASH_CASES = {
    # the module's (2, 128, 4, 32) shape under caps of 64, as always
    # tested: sub-tile = tile, a 2 x 2 grid of interior, diagonal and dead
    "fwd-noncausal": (B, S, S, H, H, D, jnp.float32, False, 64),
    "fwd-causal": (B, S, S, H, H, D, jnp.float32, True, 64),
    "gqa-2to1": (B, S, S, H, 2, D, jnp.float32, True, None),
    # one 512 tile walked in 128-wide sub-tiles, all three kinds, the
    # walk decided while tracing (a one-tile grid)
    "one-tile-of-sub-tiles": (1, 512, 512, 2, 2, D, jnp.float32, True, None),
    # fetch tile 256 > sub-tile 128 on a 2 x 2 grid: dead tiles fetch
    # nothing, the interior tile runs whole, the diagonal ones in strips
    "tile-larger-than-sub-tile": (1, 512, 512, 1, 1, D, jnp.float32, True,
                                  256),
    "sq-shorter": (1, 128, 256, 2, 2, D, jnp.float32, True, 64),
    "sq-longer": (1, 256, 128, 2, 2, D, jnp.float32, True, 64),
    "sq-longer-rect-tile": (1, 256, 128, 1, 1, D, jnp.float32, True, 128),
    "gqa-4to1": (1, 128, 128, 8, 2, D, jnp.float32, True, 64),
    "bf16-d128": (1, 256, 256, 2, 2, 128, jnp.bfloat16, True, None),
    # a latent mixer's head sizes (v 128 wide) on a 2 x 2 grid of 128 tiles
    "bf16-d192-v128": (1, 256, 256, 2, 2, 192, jnp.bfloat16, True, 128, 128),
    "noncausal-rect": (1, 128, 256, 2, 2, D, jnp.float32, False, 64),
    # no block >= 8 tiles 100 rows: the XLA reference answers
    "untileable": (1, 100, 100, 2, 2, D, jnp.float32, True, None),
}


@pytest.mark.parametrize("grads", [False, True], ids=["value", "grads"])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention(case, grads):
    """Value and the three gradients against ``mha_reference``."""
    b, sq, sk, h, h_kv, d, dtype, causal, cap, *dv = FLASH_CASES[case]
    q, k, v = _qkv(b, sq, sk, h, h_kv, d, dtype)
    v = v[..., :dv[0]] if dv else v
    caps = {} if cap is None else {"block_q": cap, "block_k": cap}
    rep = h // h_kv
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal, **caps)
    ref = lambda q, k, v: mha_reference(
        q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2), causal=causal)
    f32 = lambda x: x.astype(jnp.float32)
    # bf16 carries 8 bits: one ulp of an O(1) output is 2**-8
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    if not grads:
        err = jnp.max(jnp.abs(f32(jax.jit(flash)(q, k, v))
                              - f32(jax.jit(ref)(q, k, v))))
        assert err < tol
        return
    _, got = _value_and_grads(flash, _sum_of_squares, q, k, v)
    _, want = _value_and_grads(ref, _sum_of_squares, q, k, v)
    for a, w in zip(got, want):
        scale = max(1.0, float(jnp.max(jnp.abs(f32(w)))))
        assert jnp.max(jnp.abs(f32(a) - f32(w))) < 10 * tol * scale


@pytest.mark.parametrize("tiles", [
    (64, 64, 32, 32), (128, 128, 32, 32), (32, 128, 32, 64),
    (128, 32, 64, 16), (64, 128, 16, 64), (128, 64, 64, 16),
    # strips whose masked part is all of them; an interior tile of 4 strips
    (64, 64, 64, 16), (32, 64, 16, 16)],
    ids=lambda t: "x".join(map(str, t)))
def test_flash_tile_schedules(qkv, causal_reference, tiles):
    """Any fetch tile and sub-tile give the reference's numbers: square
    and rectangular, sub-tile wider than tall and the reverse, tiles that
    straddle the diagonal at several offsets, interior tiles that the
    dk/dv kernel walks in its own loop."""
    from ray_tpu.ops.attention import _flash

    q, k, v = qkv
    out, got = _value_and_grads(
        lambda *a: _flash(*a, D ** -0.5, True, tiles, True),
        _sum_of_squares, q, k, v)
    want, ref = causal_reference
    assert jnp.max(jnp.abs(out - want)) < 1e-4
    for a, w in zip(got, ref):
        assert jnp.max(jnp.abs(a - w)) < 1e-3


def _ratio(sq, sk, tiles):
    n = causal_tile_counts(sq, sk, *tiles)
    return n["executed_pairs"] / n["causal_pairs"], n


@pytest.mark.parametrize("seq,most,masked", [
    (4096, 1.13, 0.25),    # mistral7b-train-s4096, the mesh cell's chips
    (512, 1.5, 1.0),       # mistral7b-train-s512
    (2048, 1.25, 0.25), (1024, 1.25, 1.0), (32768, 1.02, 0.02)])
def test_causal_tile_counts_at_the_cells_shapes(seq, most, masked):
    """What the kernels compute over what the mask leaves, at the sizes
    ``choose_tiles`` picks for d=128 bf16."""
    tiles = choose_tiles(seq, seq, True, 128, jnp.bfloat16)
    ratio, n = _ratio(seq, seq, tiles)
    assert 1.0 <= ratio <= most
    assert n["diagonal"] <= masked * (n["interior"] + n["diagonal"])
    assert n["causal_pairs"] == seq * (seq + 1) // 2
    # Parent schedule, for the record: whole 512 x 1024 tiles.
    old = _ratio(seq, seq, (min(seq, 512), min(seq, 1024)) * 2)[0]
    assert ratio < old


def test_causal_tile_counts_without_a_mask():
    tiles = choose_tiles(4096, 4096, False, 128, jnp.bfloat16)
    assert tiles == (512, 1024, 512, 1024)      # what the parent compiled
    n = causal_tile_counts(4096, 4096, *tiles, causal=False)
    assert (n["dead"], n["diagonal"], n["interior"]) == (0, 0, 32)
    assert n["executed_pairs"] == n["causal_pairs"] == 4096 * 4096


@pytest.mark.parametrize("sq,sk", [(32, 32), (48, 16), (16, 48), (40, 24)])
def test_no_visible_pair_in_a_dead_tile(sq, sk):
    """Exhaustive on small sizes, every sub-tile shape that divides: a
    dead sub-tile holds no pair with q >= k, an interior one no pair with
    q < k, and the counter agrees with the classification."""
    from ray_tpu.ops.attention import _tile_kind

    for sub_q in (1, 2, 4, 8, 16):
        for sub_k in (1, 2, 4, 8, 16):
            if sq % sub_q or sk % sub_k:
                continue
            kinds = {"dead": 0, "interior": 0, "diagonal": 0}
            for q0 in range(0, sq, sub_q):
                for k0 in range(0, sk, sub_k):
                    interior, diagonal = _tile_kind(k0 - q0, sub_q, sub_k)
                    assert not (interior and diagonal)
                    seen = [q >= k for q in range(q0, q0 + sub_q)
                            for k in range(k0, k0 + sub_k)]
                    if interior:
                        assert all(seen)
                    elif diagonal:
                        assert any(seen) and not all(seen)
                    else:
                        assert not any(seen)
                    kinds["interior" if interior else
                          "diagonal" if diagonal else "dead"] += 1
            n = causal_tile_counts(sq, sk, sq, sk, sub_q, sub_k)
            assert {k: n[k] for k in kinds} == kinds
            assert n["executed_pairs"] >= n["causal_pairs"] == sum(
                min(q + 1, sk) for q in range(sq))


# -- a window: the causal mask's far edge ---------------------------------------

def _seen(q, k, window):
    return k <= q and (window is None or q - k < window)


# (window, tiles) on the module's 128 positions: the window below, at and
# past the sequence; its far edge ON a sub-tile's border (a multiple of the
# sub-tile), INSIDE a sub-tile, and BETWEEN two (one short of a border: the
# border's sub-tile keeps one live pair); a window narrower than a sub-tile
# (one sub-tile straddles BOTH edges); rectangular fetch tiles, whose
# straddling offsets differ by the edge.
WINDOW_CASES = [
    (64, (64, 64, 32, 32)), (48, (64, 64, 32, 32)), (33, (64, 64, 32, 32)),
    (31, (64, 64, 32, 32)), (5, (32, 32, 16, 16)), (1, (64, 64, 32, 32)),
    (96, (128, 128, 32, 32)), (40, (32, 128, 32, 64)),
    (72, (128, 32, 64, 16)), (20, (64, 128, 16, 64)),
    (100, (128, 64, 64, 16)), (64, (32, 32, 32, 32)),
    (127, (64, 64, 32, 32)), (128, (64, 64, 32, 32)),
    (200, (64, 64, 32, 32))]


@pytest.mark.parametrize("window,tiles", WINDOW_CASES, ids=lambda t: (
    "x".join(map(str, t)) if isinstance(t, tuple) else f"w{t}"))
def test_windowed_flash_kernels_against_the_reference(qkv, window, tiles):
    """The three windowed kernels (interpreted), value and gradients,
    against ``mha_reference(window=)``; the reference itself against a
    mask written out."""
    from ray_tpu.ops.attention import _flash

    q, k, v = qkv
    out, got = _value_and_grads(
        lambda *a: _flash(*a, D ** -0.5, True, tiles, True, window),
        _sum_of_squares, q, k, v)
    want, ref = _value_and_grads(
        lambda *a: mha_reference(*a, causal=True, window=window),
        _sum_of_squares, q, k, v)
    mask = jnp.asarray([[_seen(i, j, window) for j in range(S)]
                        for i in range(S)])
    scores = jnp.where(mask, jnp.einsum("bqhd,bkhd->bhqk", q, k)
                       * D ** -0.5, -jnp.inf)
    written_out = jnp.einsum("bhqk,bkhd->bqhd",
                             jax.nn.softmax(scores, -1), v)
    assert jnp.max(jnp.abs(want - written_out)) < 1e-5
    assert jnp.max(jnp.abs(out - want)) < 1e-4
    for a, w in zip(got, ref):
        assert jnp.max(jnp.abs(a - w)) < 1e-3


@pytest.mark.parametrize("window", [None, 128, 200], ids=str)
def test_without_a_live_window_the_call_is_the_plain_one_bit_for_bit(
        qkv, window):
    """``window=None`` — and a window that reaches every key — run the
    plain kernels under their plain names: the same bits out, the same
    gradients, no ``_win`` in the program."""
    q, k, v = qkv
    plain = lambda *a: flash_attention(*a, causal=True, block_q=64,
                                       block_k=64)
    windowed = lambda *a: flash_attention(*a, causal=True, block_q=64,
                                          block_k=64, window=window)
    assert jnp.array_equal(plain(q, k, v), windowed(q, k, v))
    loss = lambda fn: lambda *a: (fn(*a) ** 2).sum()
    for a, w in zip(jax.grad(loss(windowed), (0, 1, 2))(q, k, v),
                    jax.grad(loss(plain), (0, 1, 2))(q, k, v)):
        assert jnp.array_equal(a, w)
    text = jax.jit(jax.grad(loss(windowed), (0, 1, 2))).lower(
        q, k, v).as_text(debug_info=True)
    assert "flash_fwd" in text and "flash_dkv" in text
    assert "flash_dq" not in text   # ONE backward kernel writes all three
    assert not any(name + "_win" in text
                   for name in ("flash_fwd", "flash_dkv"))
    live = jax.jit(jax.grad(loss(lambda *a: flash_attention(
        *a, causal=True, window=64)), (0, 1, 2))).lower(q, k, v).as_text(
            debug_info=True)
    for name in ("flash_fwd_win", "flash_dkv_win"):
        assert name in live
    assert "flash_dq" not in live
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, window=0)


@pytest.mark.parametrize("sq,sk", [(32, 32), (48, 16), (16, 48), (40, 24)])
def test_tile_kinds_and_counts_under_a_window_against_a_brute_count(sq, sk):
    """Exhaustive on small sizes, every sub-tile shape that divides and
    every window from 1 past the sequence: a dead sub-tile holds no pair
    inside the window, an interior one none outside it, the edges named
    are the edges straddled, and the counter agrees with a count of the
    pairs one by one."""
    from ray_tpu.ops.attention import _edges, _tile_kind

    for window in (1, 2, 3, 7, 8, 9, 16, 17, 31, 40, 64):
        for sub_q in (1, 2, 4, 8, 16):
            for sub_k in (1, 2, 4, 8, 16):
                if sq % sub_q or sk % sub_k:
                    continue
                kinds = {"dead": 0, "interior": 0, "diagonal": 0}
                for q0 in range(0, sq, sub_q):
                    for k0 in range(0, sk, sub_k):
                        interior, edge = _tile_kind(k0 - q0, sub_q, sub_k,
                                                    window)
                        assert not (interior and edge)
                        pairs = [(q, k) for q in range(q0, q0 + sub_q)
                                 for k in range(k0, k0 + sub_k)]
                        seen = [_seen(q, k, window) for q, k in pairs]
                        if interior:
                            assert all(seen)
                        elif edge:
                            assert any(seen) and not all(seen)
                            near, far = _edges(k0 - q0, sub_q, sub_k, window)
                            assert near == any(k > q for q, k in pairs)
                            assert far == any(q - k >= window
                                              for q, k in pairs)
                        else:
                            assert not any(seen)
                        kinds["interior" if interior else
                              "diagonal" if edge else "dead"] += 1
                n = causal_tile_counts(sq, sk, sq, sk, sub_q, sub_k,
                                       window=window)
                assert {k: n[k] for k in kinds} == kinds
                assert n["executed_pairs"] >= n["causal_pairs"] == sum(
                    _seen(q, k, window) for q in range(sq)
                    for k in range(sk))


def test_the_windowed_grid_fetches_no_tile_beyond_either_edge():
    """The kv blocks a q tile's grid steps name — the forward's and the
    backward's, whose q tile is step ``t`` of a KV head — are its live
    tiles and no other: a dead step names its nearest live neighbour's
    block, which Pallas does not copy again.  At the cell's shape: 8192
    positions under 4096 in 2048 x 2048 tiles."""
    from ray_tpu.ops.attention import _grid_and_specs

    for s, window, bq, bk in ((8192, 4096, 2048, 2048), (512, 100, 64, 128),
                              (512, 300, 128, 32), (256, 64, 64, 64)):
        qt = jax.ShapeDtypeStruct((1, 1, s, 128), jnp.bfloat16)
        (nq, nk), specs = _grid_and_specs(qt, qt, qt, True, (bq, bk, 8, 8),
                                          window)
        assert (nq, nk) == (s // bq, s // bk)
        live = [[any(_seen(q, k, window)
                     for q in (i * bq, i * bq + bq - 1)
                     for k in range(j * bk, j * bk + bk))
                 or any(_seen(q, k, window)
                        for q in range(i * bq, i * bq + bq)
                        for k in (j * bk, j * bk + bk - 1))
                 for j in range(nk)] for i in range(nq)]
        for i in range(nq):
            for name in ("k_j", "v_j", "k_t", "v_t"):
                named = {int(specs[name].index_map(0, 0, i, j)[2])
                         for j in range(nk)}
                assert named == {j for j in range(nk) if live[i][j]}, (s, i)
            # the q side of the backward stays put while the kv tiles pass
            for name in ("q_t", "o_t", "stat_t"):
                at = 3 if name == "stat_t" else 2
                assert {int(specs[name].index_map(0, 0, i, j)[at])
                        for j in range(nk)} == {i}
    # the plain grid names what it named
    (nq, nk), specs = _grid_and_specs(qt, qt, qt, True, (64, 64, 8, 8))
    for name in ("k_j", "k_t"):
        assert {int(specs[name].index_map(0, 0, 1, j)[2])
                for j in range(nk)} == {0, 1}


def test_causal_tile_counts_at_the_windowed_cells_shape():
    """trinity-train-s8192: 8192 positions under a window of 4096 at the
    tiles ``choose_tiles`` picks for d=128 bf16 — 25.17 M pairs of the
    33.56 M causal ones, 1.062 times of them computed, a third of the
    sub-tiles dead on the far side."""
    tiles = choose_tiles(8192, 8192, True, 128, jnp.bfloat16, window=4096)
    assert tiles == choose_tiles(8192, 8192, True, 128, jnp.bfloat16) == (
        2048, 2048, 256, 256)
    n = causal_tile_counts(8192, 8192, *tiles, window=4096)
    plain = causal_tile_counts(8192, 8192, *tiles)
    assert n["causal_pairs"] == 4096 * 4097 // 2 + 4096 * 4096 == 25167872
    assert plain["causal_pairs"] == 33558528
    assert n["executed_pairs"] / n["causal_pairs"] == pytest.approx(
        1.0624, abs=1e-4)
    assert (n["interior"], n["diagonal"], n["dead"]) == (360, 48, 616)
    assert (plain["interior"], plain["diagonal"], plain["dead"]) == (
        496, 32, 496)
    # at the window's length and below, the window counts as the mask does
    short = choose_tiles(4096, 4096, True, 128, jnp.bfloat16)
    assert causal_tile_counts(4096, 4096, *short, window=4096) == \
        causal_tile_counts(4096, 4096, *short)


# -- heads where the model leaves them, a KV head by the index map -----------

# (rep, q/k head, v head, mode) on 128 positions in 64 x 64 tiles (a 2 x 2
# grid with a dead tile under the mask: the backward kernel's composite axis
# of ``rep x nq`` steps crosses both a head and a dead tile wherever rep > 1).
# Head sizes 128 and 256 fill whole lane blocks and are read IN PLACE out
# of ``(b, s, heads x d)``; 64 and a latent mixer's 192 / 128 are turned
# round to ``(b, heads, s, d)`` as always.  Every rep meets every head
# size, and every mode (the window's far edge inside a sub-tile, 40, and on
# a tile's border, 64) both addressings and every rep.
_HEAD_MODES = ("causal", "window", "full")
HEAD_CASES = [
    (rep, d, dv, _HEAD_MODES[(i + j) % 3])
    for i, rep in enumerate((1, 4, 6, 16))
    for j, (d, dv) in enumerate(((128, 128), (256, 256), (64, 64),
                                 (192, 128)))
] + [(6, 128, 128, "causal"), (6, 128, 128, "window"), (4, 128, 128, "full"),
     (16, 128, 128, "window"), (4, 256, 256, "causal"), (4, 64, 64, "window"),
     (6, 192, 128, "window"), (16, 64, 64, "window"), (1, 256, 128, "causal")]


def _heads_qkv(rep, d, dv, sq=128, sk=128, h_kv=2):
    q, k, v = _qkv(1, sq, sk, rep * h_kv, h_kv, d, jnp.float32)
    return q, k, v[..., :dv]


def _assert_close(out, want, grads, ref_grads, tol=1e-4):
    """The value within ``tol``, each gradient within ``tol`` of its
    reference's largest entry (of 1 at least)."""
    assert jnp.max(jnp.abs(out - want)) < tol
    for a, w in zip(grads, ref_grads):
        assert jnp.max(jnp.abs(a - w)) < tol * max(1.0, float(
            jnp.max(jnp.abs(w))))


def _check_heads(rep, d, dv, sq, sk, kw):
    q, k, v = _heads_qkv(rep, d, dv, sq, sk)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64,
                                            **kw)
    ref = lambda q, k, v: mha_reference(
        q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2), **kw)
    loss = lambda out: jnp.sum(jnp.sin(out))
    out, grads = _value_and_grads(flash, loss, q, k, v)
    want, ref_grads = _value_and_grads(ref, loss, q, k, v)
    assert out.shape == (1, sq, 2 * rep, dv)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    _assert_close(out, want, grads, ref_grads)


@pytest.mark.parametrize("rep,d,dv,mode", HEAD_CASES,
                         ids=lambda x: str(x))
def test_flash_reads_heads_where_they_stand(rep, d, dv, mode):
    """Value AND the three gradients against ``mha_reference`` on k, v
    repeated by hand: the kernels read KV head ``h // rep``, and dk, dv
    come back at the KV heads' own count, each the sum over its group."""
    window = {"window": 40 if rep % 4 else 64}.get(mode)
    _check_heads(rep, d, dv, 128, 128,
                 dict(causal=mode != "full", window=window))


# (d, dv, sq, sk, causal, window) at rep 8 in 64 x 64 tiles: FOUR kv tiles,
# so the one backward kernel comes back to a block of its whole-sequence
# dk / dv from 8 heads x up to 4 q tiles, each visit after the kv axis has
# passed over the other blocks; both addressings; a window wider than a
# fetch tile and one narrower; sq != sk both ways; no mask.
REVISIT_CASES = [
    (128, 128, 256, 256, True, None), (64, 64, 256, 256, True, 100),
    (128, 128, 256, 256, True, 40), (192, 128, 128, 256, True, None),
    (64, 64, 256, 128, True, None), (128, 128, 128, 256, False, None)]


@pytest.mark.parametrize("d,dv,sq,sk,causal,window", REVISIT_CASES,
                         ids=lambda x: str(x))
def test_the_backward_kernel_revisits_dk_and_dv_across_its_outer_axis(
        d, dv, sq, sk, causal, window):
    """dq, dk and dv of ONE kernel against the reference where a KV head's
    gradient block is written to from many grid steps that are not
    consecutive."""
    _check_heads(8, d, dv, sq, sk, dict(causal=causal, window=window))


def test_a_backward_whose_dk_and_dv_do_not_fit_vmem_is_refused_by_name():
    """The one limit the single backward kernel adds: a KV head's whole dk
    and dv stay in VMEM, so past 43690 keys of 128-wide bf16 heads the
    GRADIENT is refused while it is traced, with the bound and the way out
    in the message; the forward pass has no such limit, and Mellum2's
    16384 keys are far inside it."""
    from ray_tpu.ops import attention

    def grad_shapes(s, dtype, d=128):
        q = jax.ShapeDtypeStruct((1, s, 2, d), dtype)
        k = jax.ShapeDtypeStruct((1, s, 1, d), dtype)
        return jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
            q, k, v).astype(jnp.float32).sum(), (0, 1, 2)), q, k, k)

    assert grad_shapes(40960, jnp.bfloat16)[1].shape == (1, 40960, 1, 128)
    assert grad_shapes(16384, jnp.float32)[1].shape == (1, 16384, 1, 128)
    for s, dtype, d in ((49152, jnp.bfloat16, 128), (40960, jnp.float32, 128),
                        (32768, jnp.bfloat16, 192)):   # 192 pads to 256
        with pytest.raises(ValueError, match="_RESIDENT_BYTES.*ring_attention"):
            grad_shapes(s, dtype, d)
    big = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)
    assert jax.eval_shape(lambda q: flash_attention(q, q, q), big).shape == (
        1, 65536, 1, 128)
    assert 4 * attention._RESIDENT_BYTES < 3 * attention._VMEM_LIMIT


def _eqns_outside_kernels(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, the bodies of the
    ``pallas_call``s left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("d,in_place", [(128, True), (64, False)],
                         ids=["128-in-place", "64-turned-round"])
def test_round_the_in_place_calls_nothing_is_transposed_or_repeated(
        d, in_place):
    """At a head of whole lane blocks the program round the two kernels
    holds NO transpose (``delta``, the float a row that was turned round
    here until PR 77, is made inside the backward kernel) and no k, v, dk or
    dv at q's head count; a head of 64 lanes keeps the transposes, and loses
    the repeat all the same.  In neither is a float32 array of o's size
    left outside the kernels: no ``o x do``.  (sk != sq, so a kv-side array
    is known by its length; bfloat16, so a float32 array is one the program
    made.)"""
    rep, h_kv, sq, sk = 4, 2, 128, 256
    q, k, v = (x.astype(jnp.bfloat16)
               for x in _heads_qkv(rep, d, d, sq, sk, h_kv))
    # the cotangent comes in as data: no loss whose own arrays would count
    grads = lambda q, k, v, do: jax.vjp(lambda *a: flash_attention(
        *a, causal=False, block_q=64, block_k=64), q, k, v)[1](do)
    eqns = list(_eqns_outside_kernels(jax.make_jaxpr(grads)(q, k, v, q).jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 2
    turned = [e.outvars[0].aval.shape for e in eqns
              if e.primitive.name == "transpose"]
    if in_place:
        assert turned == []
    else:       # q, k, v in; o out; do in; dq, dk, dv out
        assert len(turned) == 8 and all(len(t) == 4 for t in turned)
    repeated = rep * h_kv * sk * d      # a k at q's head count
    as_wide_as_o = math.prod(q.shape)   # b x sq x h x dv
    for e in eqns:
        for var in (*e.invars, *e.outvars):
            shape = getattr(var.aval, "shape", ())
            assert not (sk in shape and math.prod(shape) == repeated), (
                e.primitive.name, shape)
        if e.primitive.name == "pallas_call":
            continue    # the forward's log-sum-exp, 128 lanes wide: its own
        for var in e.outvars:
            assert not (var.aval.dtype == jnp.float32
                        and math.prod(var.aval.shape) >= as_wide_as_o), (
                e.primitive.name, var.aval)


# -- delta, the float a row ``sum_d o do``, made inside the backward kernel --

# The four kinds of call the one backward kernel serves, on 256 query rows:
# ``(what flash_attention takes, what mha_reference takes)``.  The data mask
# IS the window's, so that the reference can say what it should give.
_DELTA_WINDOW = 100
_DELTA_KINDS = {
    "plain": (dict(block_q=128, block_k=128), {}),
    "window": (dict(window=_DELTA_WINDOW, block_q=128, block_k=128),
               dict(window=_DELTA_WINDOW)),
    "data-mask": (None, dict(window=_DELTA_WINDOW)),
    "block-rule": (dict(block=4, block_q=64, block_k=64), dict(block=4)),
}


def _delta_call(kind, d):
    """``kind``'s attention as ``f(q, k, v) -> o`` through the kernels."""
    kw = _DELTA_KINDS[kind][0]
    if kw is not None:
        return lambda q, k, v: flash_attention(q, k, v, **kw)
    from ray_tpu.ops import sparse_attention

    at = jnp.arange(256)
    seen = (at[:, None] >= at) & (at[:, None] - at < _DELTA_WINDOW)
    sel = seen.astype(jnp.int8)[None]
    return lambda q, k, v: sparse_attention.attend(
        q, k, v, sel, sm_scale=d ** -0.5)[0]


@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("d", [128, 64], ids=["in-place", "turned-round"])
@pytest.mark.parametrize("kind", _DELTA_KINDS)
def test_delta_made_in_the_backward_kernel_survives_the_cancellation(
        kind, d, rep):
    """dq, dk and dv of every kind of call, in both addressings, one q head
    a KV head and four, against ``mha_reference``'s where ``ds = p (dp -
    delta)`` CANCELS: v and the cotangent stand round 4, so ``delta`` and
    every ``dp`` are near ``16 d`` (2048 at 128 lanes) and their difference
    is of order 10 — a ``delta`` rounded to bfloat16 on its way (8 bits: off
    by up to 8 there) misses dq and dk by whole units, a float32 one by the
    sum's last bits."""
    h_kv, s = 2, 256
    keys = jax.random.split(jax.random.PRNGKey(d + rep), 4)
    q, w = (jax.random.normal(key, (1, s, rep * h_kv, d)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, s, h_kv, d)) for key in keys[2:])
    v, w = v + 4.0, w + 4.0
    flash = _delta_call(kind, d)
    ref = lambda q, k, v: mha_reference(
        q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
        **_DELTA_KINDS[kind][1])
    loss = lambda out: jnp.sum(w * out)
    out, grads = _value_and_grads(flash, loss, q, k, v)
    want, ref_grads = _value_and_grads(ref, loss, q, k, v)
    _assert_close(out, want, grads, ref_grads)


@pytest.mark.parametrize("d", [128, 64], ids=["in-place", "turned-round"])
@pytest.mark.parametrize("kind", _DELTA_KINDS)
def test_the_backward_call_takes_o_and_do_and_one_statistic_a_row(kind, d):
    """What crosses the backward ``pallas_call``, from the jaxpr: q, k, v,
    o and do as the addressing has them, ONE float32 statistic a row as rows
    ``(b, h, 1, sq)`` — the log-sum-exp; no ``delta`` — and then the data
    mask with the keys first, or the noised stream's keys and values."""
    rep, h_kv, s = 4, 2, 256
    h = rep * h_kv
    q = jnp.zeros((1, s, h, d), jnp.bfloat16)
    k = jnp.zeros((1, s, h_kv, d), jnp.bfloat16)
    flash = _delta_call(kind, d)
    jaxpr = jax.make_jaxpr(lambda q, k, v, do: jax.vjp(flash, q, k, v)[1](
        do))(q, k, k, q).jaxpr
    calls = [e for e in _eqns_outside_kernels(jaxpr)
             if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("flash_dkv")]
    assert len(calls) == 1
    suffix = {"plain": "", "window": "_win", "data-mask": "_dsa",
              "block-rule": "_bd"}[kind]
    assert calls[0].params["name"] == "flash_dkv" + suffix
    got = [(v.aval.shape, v.aval.dtype) for v in calls[0].invars]
    bf16 = jnp.bfloat16
    wide, narrow = (((1, s, h * d), (1, s, h_kv * d)) if d == 128 else
                    ((1, h, s, d), (1, h_kv, s, d)))
    want = [(wide, bf16), (narrow, bf16), (narrow, bf16), (wide, bf16),
            (wide, bf16), ((1, h, 1, s), jnp.float32)]
    want += {"data-mask": [((1, s, s), jnp.int8)],
             "block-rule": [(narrow, bf16), (narrow, bf16)]}.get(kind, [])
    assert got == want


def test_the_backward_grid_walks_a_groups_heads_and_their_tiles():
    """The backward kernel's maps at rep 6, 4 x 4 tiles under a window, in
    both addressings: step ``t`` of KV head ``g`` names q head ``6 g + t //
    4`` and q tile ``t % 4`` whatever the kv step ``j``; k and v name head
    ``g`` and the live kv tile nearest ``j``; dk and dv name head ``g``'s
    WHOLE sequence whatever ``t`` and ``j`` (they stay in VMEM and leave
    once a KV head); the forward's grid names KV head ``h // 6``."""
    from ray_tpu.ops.attention import _grid_and_specs

    rep, h_kv, s, block, d = 6, 2, 512, 128, 128
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)
    turned = (shape(1, rep * h_kv, s, d), shape(1, h_kv, s, d))
    in_place = (shape(1, s, rep * h_kv * d), shape(1, s, h_kv * d))
    tiles = (block, block, 8, 8)
    for window in (None, 200):
        _, plain = _grid_and_specs(turned[0], turned[0], turned[0], True,
                                   tiles, window)
        (nq, nk), old = _grid_and_specs(turned[0], turned[1], turned[1],
                                        True, tiles, window)
        _, new = _grid_and_specs(in_place[0], in_place[1], in_place[1], True,
                                 tiles, window, heads=(rep * h_kv, h_kv))
        assert (nq, nk) == (4, 4)
        at = lambda spec, *idx: tuple(int(x) for x in spec.index_map(*idx))
        for g in range(h_kv):
            for t in range(rep * nq):
                head, i = g * rep + t // nq, t % nq
                for j in range(nk):
                    tile = at(plain["k_j"], 0, 0, i, j)[2]
                    for name in ("q_t", "o_t"):
                        assert at(old[name], 0, g, t, j) == (0, head, i, 0)
                        assert at(new[name], 0, g, t, j) == (0, i, head)
                    for spec in (old, new):
                        assert at(spec["stat_t"], 0, g, t, j) == (
                            0, head, 0, i)
                    for name in ("k_t", "v_t"):
                        assert at(old[name], 0, g, t, j) == (0, g, tile, 0)
                        assert at(new[name], 0, g, t, j) == (0, tile, g)
                    for name in ("k_all", "v_all"):
                        assert at(old[name], 0, g, t, j) == (0, g, 0, 0)
                        assert at(new[name], 0, g, t, j) == (0, 0, g)
        for h_ in range(rep * h_kv):
            for i in range(nq):
                for j in range(nk):
                    tile = at(plain["k_j"], 0, 0, i, j)[2]
                    for name in ("k_j", "v_j"):
                        assert at(old[name], 0, h_, i, j) == (
                            0, h_ // rep, tile, 0)
                        assert at(new[name], 0, h_, i, j) == (
                            0, tile, h_ // rep)
                    assert at(new["q_i"], 0, h_, i, j) == (0, i, h_)
                    assert at(new["row_i"], 0, h_, i, j) == (0, h_, i, 0)
        assert new["q_i"].block_shape == (None, block, d)
        assert old["q_i"].block_shape == (None, None, block, d)
        assert new["k_all"].block_shape == (None, s, d)
        assert old["k_all"].block_shape == (None, None, s, d)
        # at one q head a KV head the two grids are one
        for name, twin in (("q_i", "q_t"), ("k_j", "k_t"), ("o_i", "o_t")):
            for i in range(nq):
                for j in range(nk):
                    assert at(plain[name], 0, 3, i, j) == at(
                        plain[twin], 0, 3, i, j)


def test_an_untileable_gqa_call_repeats_for_the_reference_alone():
    """No block of 64 rows or fewer tiles 100: the XLA reference answers,
    on k and v repeated — the one place the flash path still repeats
    them."""
    q, k, v = _heads_qkv(4, 128, 128, 100, 100)
    flash = lambda *a: flash_attention(*a, block_q=64, block_k=64)
    assert "pallas_call" not in str(jax.make_jaxpr(flash)(q, k, v))
    want = mha_reference(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2))
    assert jnp.max(jnp.abs(flash(q, k, v) - want)) < 1e-5
    with pytest.raises(ValueError):     # 8 q heads over 3 kv heads
        flash(q, k[:, :, :1].repeat(3, 2), v[:, :, :1].repeat(3, 2))


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_attention(qkv, impl, causal):
    from jax.sharding import NamedSharding, PartitionSpec as P
    q, k, v = qkv
    mesh = make_mesh(MeshConfig(dp=1, sp=4, tp=2))
    sh = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    fn = ring_attention if impl == "ring" else ulysses_attention
    kw = {} if impl == "ring" else {"use_flash": False}
    ref = mha_reference(q, k, v, causal=causal)
    with use_mesh(mesh):
        out = fn(qs, ks, vs, causal=causal, mesh=mesh, **kw)
        assert jnp.max(jnp.abs(out - ref)) < 1e-4
        # grads through the ring/all-to-all
        loss = jax.jit(jax.grad(
            lambda a, b, c: (fn(a, b, c, causal=causal, mesh=mesh,
                                **kw) ** 2).sum(), (0, 1, 2)))
        got = loss(qs, ks, vs)
    want = jax.grad(
        lambda a, b, c: (mha_reference(a, b, c, causal=causal) ** 2).sum(),
        (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-3


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    w = jnp.ones(16) * 2.0
    out = rms_norm(x, w)
    expected = x / jnp.sqrt(jnp.mean(x ** 2, -1, keepdims=True) + 1e-6) * 2.0
    assert jnp.allclose(out, expected, atol=1e-5)


def test_rope_offset_consistency():
    """Slicing full-range tables == computing with an offset (the 'sp'
    invariant ring attention relies on)."""
    cos_full, sin_full = rope(64, 32)
    cos_off, sin_off = rope(32, 32, offset=32)
    assert jnp.allclose(cos_full[32:], cos_off, atol=1e-6)
    assert jnp.allclose(sin_full[32:], sin_off, atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32))
    full = apply_rope(x, cos_full, sin_full)
    part = apply_rope(x[:, 32:], cos_off, sin_off)
    assert jnp.allclose(full[:, 32:], part, atol=1e-5)


_YARN = {"rope_type": "yarn", "factor": 16.0,
         "original_max_position_embeddings": 8}


@pytest.mark.parametrize("scale", [None, 128 ** -0.5 * math.log2(math.e)],
                         ids=["plain", "prescale"])
@pytest.mark.parametrize("tables", ["offset", "yarn"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [1, 4, 8])
@pytest.mark.parametrize("rows,d", [(1, 128), (3, 128), (1, 256), (2, 256)])
def test_the_rope_kernel_is_apply_rope_bit_for_bit(rows, d, heads, dtype,
                                                   tables, scale):
    """The rotation's kernel on ``(b, s, heads x d)`` (interpreted here) is
    ``apply_rope`` on ``(b, s, heads, d)`` — then times the flash kernels'
    pre-scale, rounded as ``ops/attention.py::_forward`` rounds it —, values
    and the gradient w.r.t. x, to the BIT (-0.0 is not 0.0 here), each side
    ONE compiled program: the same products and sums in the same precision
    — the backward pass's too, where autodiff rounds each product to x's
    type before it adds them —, under tables that start at an 'sp' rank's
    offset and under YaRN's frequencies times its factor.  The CPU's compiler
    contracts a product and a sum into a fused multiply-add, another one in
    each program, and float32 would then differ in the last place: the
    float32 cases draw x, g, the scale and the tables from bfloat16's
    numbers, whose products are exact, so fused or not rounds alike."""
    s = 24
    cos, sin = (rope(s, d, 1e4, offset=40) if tables == "offset"
                else scaled_rope(s, d, 1e4, _YARN))
    assert tables == "offset" or float(jnp.max(jnp.abs(cos))) > 1.0
    if scale is not None:
        scale = attention.q_prescale(scale / math.log2(math.e), jnp.bfloat16)
    if dtype == jnp.float32:
        cos, sin = (t.astype(jnp.bfloat16).astype(dtype) for t in (cos, sin))
    x, g = (jax.random.normal(k, (rows, s, heads * d), jnp.float32
                              ).astype(jnp.bfloat16).astype(dtype)
            for k in jax.random.split(jax.random.PRNGKey(d + heads)))
    assert rotary.fits(s, d)

    def by_head(x):
        y = apply_rope(x.reshape(rows, s, heads, d), cos, sin).reshape(x.shape)
        return y if scale is None else (y * scale).astype(y.dtype)

    def kernel(x):
        return rotary.rope_rotate(x, *rotary.lane_tables(cos, sin), d, scale)

    def both(fn):
        def weighed(x):
            y = fn(x)
            return jnp.sum((y * g).astype(jnp.float32)), y
        (_, y), dx = jax.jit(jax.value_and_grad(weighed, has_aux=True))(x)
        return y, dx

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a, jnp.uint16 if dtype == jnp.bfloat16 else jnp.uint32)

    (want, dx_want), (got, dx_got) = both(by_head), both(kernel)
    assert got.dtype == dtype and jnp.array_equal(bits(got), bits(want))
    assert not jnp.array_equal(got, x)          # it does rotate
    assert dx_got.dtype == dtype
    assert jnp.array_equal(bits(dx_got), bits(dx_want))


@pytest.mark.parametrize("s", [64, 7], ids=["kernels", "no-tile"])
def test_flash_attention_takes_q_prescaled_with_its_own_gradient(s):
    """``q_prescaled``: q comes times ``q_prescale`` (the rotation's kernel
    applied it on its way out) and the kernels multiply nothing; output and
    the gradients to k and v are those of the plain call on q, the gradient
    to the SCALED q that of the plain call's to q over the scale — through
    the kernels and, where no block tiles the sequence, the XLA form."""
    q, k, v, g = (jax.random.normal(key, (2, s, 4, 32)) for key in
                  jax.random.split(jax.random.PRNGKey(s), 4))
    c = attention.q_prescale(32 ** -0.5, q.dtype)

    def run(q, prescaled):
        def weighed(q, k, v):
            return jnp.sum(g * flash_attention(q, k, v,
                                               q_prescaled=prescaled))
        return jax.jit(jax.value_and_grad(weighed, (0, 1, 2)))(q, k, v)

    want, (dq, dk, dv) = run(q, False)
    got, (dqs, dk_got, dv_got) = run(q * c, True)
    assert jnp.allclose(got, want, rtol=1e-5)
    for a, b in ((dqs * c, dq), (dk_got, dk), (dv_got, dv)):
        assert jnp.max(jnp.abs(a - b)) < 1e-5 * jnp.max(jnp.abs(b))


def test_moe_routing_mass_conservation():
    """Dropless: every token's output is the gate-weighted sum of its k
    experts — with experts that return their input unchanged in the sum of
    a constant, the mass a token receives is its top-k gate mass."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 16))
    rw = jax.random.normal(jax.random.PRNGKey(1), (16, 4)) * 0.1
    wg = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 32)) * 0.1
    wu = jax.random.normal(jax.random.PRNGKey(3), (4, 16, 32)) * 0.1
    wd = jax.random.normal(jax.random.PRNGKey(4), (4, 32, 16)) * 0.1
    norm = jnp.ones((16,))
    layer = jax.jit(lambda *w: moe_block(x, norm, rw, *w, num_selected=2))
    out, stats = layer(wg, wu, wd)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all()
    assert float(stats["aux_loss"]) > 0
    assert float(stats["dropped"]) == 0  # all 128 assignments computed
    # All experts equal: the layer is one expert scaled by each token's
    # top-2 gate mass, whatever the routing.
    same = [jnp.broadcast_to(w[:1], w.shape) for w in (wg, wu, wd)]
    out, _ = layer(*same)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    gates, _ = jax.lax.top_k(jax.nn.softmax(h @ rw, -1), 2)
    one = (jax.nn.silu(h @ wg[0]) * (h @ wu[0])) @ wd[0]
    assert jnp.allclose(out - x, gates.sum(-1, keepdims=True) * one,
                        atol=1e-5)
    # gradient flows to every expert weight
    g = jax.jit(jax.grad(lambda w: (layer(w, wu, wd)[0] ** 2).sum()))(wg)
    assert float(jnp.abs(g).sum(axis=(1, 2)).min()) > 0
