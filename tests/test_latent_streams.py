"""Xing4.0's mechanisms at CPU size: latent attention at two head sizes,
the n-stream residual and its Sinkhorn maps, sigmoid-scored experts with a
selection bias, a shared expert and a held share, the predicted-ahead
module — the program (``ray_tpu/models/llama.py`` and its ops) against the
plain reference (``benchmark/reference/xing4.py``) on seeded weights."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import xing4
from ray_tpu.models.llama import (
    LlamaConfig, init_params, loss_fn, param_logical_axes)
from ray_tpu.ops import attention
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.layers import sinkhorn, yarn_inv_freq, yarn_mscale
from ray_tpu.ops.moe import update_selection_bias
from ray_tpu.train.core import STEP_SCOPES
import tiny_models
from tiny_models import (
    ROWS, XING4_SCALING as SCALING, against_the_reference, expert_layer,
    fault_ids, program, share, shares_add_up, stands_apart,
    train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "xing4.0-29b-a4b-1of8"
CONF, TOKENS = ROWS["xing4"].conf, ROWS["xing4"].tokens
tiny = functools.partial(tiny_models.tiny, "xing4")


# -- 1a: runs of (mixer, FFN) -------------------------------------------------

def test_runs_are_of_mixer_and_ffn_and_hold_only_their_kind():
    cfg = tiny()
    assert cfg.kind_runs == ((("latent", "dense"), 2), (("latent", "moe"), 2))
    assert cfg.layer_runs == (("latent", 2), ("latent", 2))
    assert cfg.mtp_runs == ((("latent", "moe"), 1),)
    dense, moe = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    assert dense["w_gate"].shape == (2, 64, 96) and "router" not in dense
    assert moe["w_gate"].shape == (2, 4, 64, 32)         # the 4 held
    assert moe["router"].shape == (2, 64, 16)            # ALL the experts
    assert moe["router_bias"].dtype == jnp.float32
    assert moe["shared_down"].shape == (2, 32, 64)
    assert moe["hc_attn_proj"].shape == (2, 4 * 64, 2 * 4 + 16)
    axes = param_logical_axes(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(x, (str, type(None))) for x in a)))
    # the models there were keep their runs and their stacks
    assert LlamaConfig(num_layers=3).kind_runs == (
        (("attention", "dense"), 3),)
    assert LlamaConfig(num_layers=2, num_experts=4).kind_runs == (
        (("attention", "moe"), 2),)


# -- the whole model against the reference ------------------------------------

def test_loss_parts_and_gradients_equal_the_plain_reference():
    _, parts, _, ours = against_the_reference(
        "xing4", parts=("loss", "mtp_loss", "moe_held_share"))
    assert float(parts["moe_dropped"]) == 0.0
    # no gradient reaches a selection bias
    assert not np.any(np.asarray(ours["layers"][1]["router_bias"]))


def test_flash_kernels_and_the_checkpoint_give_the_same_loss():
    params = program("xing4").params
    plain, _ = program("xing4").loss(params)
    kernels, _ = program("xing4", attn_impl="flash", remat=True).loss(params)
    np.testing.assert_allclose(kernels, plain, rtol=1e-5)


# what each part of the model is worth to the loss (the row's ``faults``):
# the program with the part changed must stand apart from the reference by
# far more than the check's tolerance (1e-4), or the check could not see it
@pytest.mark.parametrize("fault", fault_ids("xing4"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    stands_apart("xing4", fault)


@pytest.mark.parametrize("leaf", fault_ids("xing4", "leaf"))
def test_a_zeroed_leaf_stands_apart_from_the_reference(leaf):
    stands_apart("xing4", leaf)


# -- 1b: the flash kernels at two head sizes -----------------------------------

def _qkv(seq, d, dv, heads=2, rows=1, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(keys[0], (rows, seq, heads, d), dtype),
            jax.random.normal(keys[1], (rows, seq, heads, d), dtype),
            jax.random.normal(keys[2], (rows, seq, heads, dv), dtype))


HEADS = [(192, 128), (128, 128), (64, 64), (24, 16), (16, 32)]
# How a "k" strip of the backward kernel can come: (seq, causal, tiles).
# "chosen" goes through ``flash_attention`` and ``choose_tiles``.
STRIPS = {
    "chosen": None,
    # a 2 x 2 grid: the interior tile comes whole and is walked by the
    # kernel's own loop, the diagonal ones in strips of falling extent
    "interior-tile-looped": (256, True, (128, 128, 32, 32)),
    # block_q != block_k: tiles straddle the diagonal at two offsets each
    "straddling-wide-k": (256, True, (64, 128, 16, 64)),     # -64, 0
    "straddling-tall-q": (256, True, (128, 64, 64, 16)),     # 0, 64
    # sub_q = block_q: the masked part of a strip is all of it
    "all-of-a-strip-masked": (128, True, (64, 64, 64, 16)),
    "one-tile-grid": (128, True, (128, 128, 32, 32)),
    "no-mask": (128, False, (64, 128, 64, 128)),
}
_FLASH_CASES = (
    [(d, dv, which, "chosen") for d, dv in HEADS
     for which in ("forward", "dq", "dk", "dv")]
    + [(d, dv, which, strips) for d, dv in HEADS for strips in STRIPS
       if strips != "chosen" for which in ("dq", "dk", "dv")])


def _value_and_grads(fn, q, k, v):
    def scalar(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(jnp.sin(out)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, (0, 1, 2), has_aux=True))(q, k, v)
    return (out, *grads)


@functools.lru_cache(maxsize=None)     # the reference's: whatever the tiles
def _reference_grads(d, dv, seq, causal):
    return _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=causal,
                                      sm_scale=0.7 * d ** -0.5),
        *_qkv(seq, d, dv))


@functools.lru_cache(maxsize=None)     # one backward pass a (heads, strips)
def _flash_grads(d, dv, strips):
    """((value, dq, dk, dv) of the kernels, the same of the reference)."""
    seq, causal, tiles = STRIPS[strips] or (256, True, None)
    q, k, v = _qkv(seq, d, dv)
    scale = 0.7 * d ** -0.5
    if tiles is None:
        flash = lambda q, k, v: flash_attention(
            q, k, v, sm_scale=scale, block_q=128, block_k=64)
    else:
        flash = lambda q, k, v: attention._flash(
            q, k, v, scale, causal, tiles, True)
    return (_value_and_grads(flash, q, k, v),
            _reference_grads(d, dv, seq, causal))


@pytest.mark.parametrize(
    "d,dv,which,strips", _FLASH_CASES,
    ids=[f"{d}x{dv}-{which}-{strips}" for d, dv, which, strips in _FLASH_CASES])
def test_flash_takes_a_v_head_apart_from_the_qk_head(d, dv, which, strips):
    """Value and gradients against ``mha_reference`` at head sizes equal
    and apart, whole and fractions of a lane block; dq, dk and dv (ONE
    kernel, whose scores are transposed and whose interior tile is a loop)
    on every kind of part a "k" strip has."""
    got, want = _flash_grads(d, dv, strips)
    arg = ("forward", "dq", "dk", "dv").index(which)
    if which == "forward":
        assert got[0].shape == (1, 256, 2, dv)
    np.testing.assert_allclose(got[arg], want[arg], atol=2e-5, rtol=2e-5)


def test_flash_dkv_is_one_loop_over_an_interior_tile():
    """ONE body for every shape, and ONE kernel for the three gradients.
    On a grid with an interior tile it holds that tile's loop (the strips,
    not unrolled) beside the two over its whole-sequence dk / dv (zeroed,
    written out); on a one-tile grid the strips are static.  Its per-row
    statistic, the log-sum-exp alone, is rows ``(b, h, 1, sq)``: the
    forward's lane-replicated ``(b, h, sq, 128)`` is an output of
    ``flash_fwd`` and an operand of nothing."""
    q, k, v = _qkv(256, 24, 16)

    def backward(tiles):
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: attention._flash(
            q, k, v, 1.0, True, tiles, True).sum(), (0, 1, 2)))(q, k, v)
        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        return str(jaxpr), calls

    loops = lambda text: text.count("scan[") + text.count("while[")
    gridded, calls = backward((128, 128, 32, 32))
    assert loops(gridded) == 3 and loops(backward((256, 256, 32, 32))[0]) == 2
    assert [e.params["name"] for e in calls] == ["flash_fwd", "flash_dkv"]
    fwd, bwd = calls
    assert (1, 2, 256, 128) in [v.aval.shape for v in fwd.outvars]
    operands = [v.aval.shape for v in bwd.invars]
    assert operands.count((1, 2, 1, 256)) == 1      # lse; no delta
    assert (1, 2, 256, 128) not in operands
    assert [v.aval.shape for v in bwd.outvars] == [
        (1, 2, 256, 24), (1, 2, 256, 24), (1, 2, 256, 16)]


def test_equal_head_sizes_give_the_kernels_the_operands_they_had():
    """With v as wide as q and k, every v-side block is the k-side block
    (and o's q's): the calls are what equal heads always gave them."""
    q = jnp.zeros((1, 2, 256, 128), jnp.bfloat16)
    _, specs = attention._grid_and_specs(q, q, q, True, (128, 128, 128, 128))
    for ours, theirs in (("v_j", "k_j"), ("v_t", "k_t"), ("o_i", "q_i"),
                         ("o_t", "q_t"), ("v_all", "k_all")):
        assert specs[ours].block_shape == specs[theirs].block_shape
        for at in ((0, 1, 0, 1), (0, 0, 1, 0)):
            assert specs[ours].index_map(*at) == specs[theirs].index_map(*at)
    assert attention.choose_tiles(8192, 8192, True, 192, jnp.bfloat16) == \
        attention.choose_tiles(8192, 8192, True, 128, jnp.bfloat16)
    q, k, v = _qkv(128, 128, 128)
    np.testing.assert_array_equal(
        flash_attention(q, k, v), flash_attention(q, k, v + 0.0))


def test_yarn_frequencies_and_scale():
    freq = np.asarray(yarn_inv_freq(64, 10000.0, factor=64, original=4096,
                                    beta_fast=32, beta_slow=1))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(freq[:10], plain[:10], rtol=1e-6)  # fast: kept
    np.testing.assert_allclose(freq[-8:], plain[-8:] / 64, rtol=1e-6)
    assert np.all(np.diff(freq) < 0)
    # the reference builds its own tables: position 1's angles ARE the
    # frequencies
    _, sin = xing4.yarn_tables(3, 64, 10000.0, dict(
        SCALING, original_max_position_embeddings=4096))
    np.testing.assert_allclose(sin[1], np.sin(freq), rtol=1e-5, atol=1e-7)
    assert yarn_mscale(64, 1) == pytest.approx(0.1 * np.log(64) + 1)
    assert yarn_mscale(1, 1) == 1.0
    conf = dict(CONF, qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert xing4.softmax_scale(conf) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


# -- 1c: the expert layer ------------------------------------------------------

_expert_layer = functools.partial(expert_layer, d=32, m=16, experts=16)


def _share(p, first, held):
    """What the chip that holds ``held`` experts from ``first`` on adds:
    the routed part alone, its step counters beside it."""
    return share(p, first, held, 4, 2.0)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 chips with 2 of 16 experts each: their routed parts, and the
    shared expert ONCE, are the whole layer as the reference has it."""
    p = _expert_layer()
    n = xing4.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = xing4.swiglu(n, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    whole, _ = xing4.expert_ffn(p["x"][None], p, k=4, factor=2.0, first=0,
                                eps=1e-6)
    parts = shares_add_up("xing4", p, _share, whole[0], k=4, shared=shared)
    # one share alone is the reference's with the same experts held
    alone, _ = xing4.expert_ffn(
        p["x"][None], {**p, **{w: p[w][6:8] for w in (
            "w_gate", "w_up", "w_down")}}, k=4, factor=2.0, first=6,
        eps=1e-6)
    np.testing.assert_allclose(parts[3][0] + shared, alone[0], atol=2e-5)


def test_the_bias_reaches_the_selection_and_not_the_gate():
    p = _expert_layer()
    pushed = dict(p, router_bias=p["router_bias"].at[5].add(10.0))
    out, stats = _share(pushed, 4, 4)
    assert int(stats["counts"][5]) == 96          # every token takes 5
    gates, experts = xing4.route(
        xing4.rms_norm(p["x"], p["mlp_norm"], 1e-6), p["router"],
        pushed["router_bias"], 4, 2.0)
    assert np.all(np.asarray(gates) < 2.0)        # sigmoid scores, not 10
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.0, rtol=1e-6)
    grad = jax.grad(lambda b: jnp.sum(_share(dict(p, router_bias=b), 4, 4)[0]
                                      ** 2))(p["router_bias"])
    assert not np.any(np.asarray(grad))


def test_selection_bias_update_sign_and_size():
    bias = jnp.array([0.0, 0.1, -0.2, 0.05])
    counts = jnp.array([10, 2, 4, 4])             # mean 5
    moved = update_selection_bias(bias, counts, 0.001)
    np.testing.assert_allclose(moved - bias, [-0.001, 0.001, 0.001, 0.001],
                               atol=1e-7)
    even = update_selection_bias(bias, jnp.array([5, 5, 5, 5]), 0.001)
    np.testing.assert_array_equal(even, bias)
    stacked = update_selection_bias(jnp.zeros((2, 4)), jnp.array(
        [[10, 2, 4, 4], [1, 9, 5, 5]]), 0.01)     # a layer a row
    np.testing.assert_allclose(stacked, [[-0.01, 0.01, 0.01, 0.01],
                                         [0.01, -0.01, 0.0, 0.0]])


def test_the_train_step_moves_the_bias_by_its_rule_and_nothing_else_does():
    _, _, before, _, _, state, metrics = train_step_reports("xing4")
    for old, new in ((before["layers"][1], state.params["layers"][1]),
                     (before["mtp"]["layers"], state.params["mtp"]["layers"])):
        step = np.asarray(new["router_bias"]) - old["router_bias"]
        assert np.all((step == 0) | np.isclose(np.abs(step), 0.001,
                                               atol=1e-6))
        assert np.any(step > 0) and np.any(step < 0)
    assert 0.0 < float(metrics["moe_held_share"]) < 1.0
    # a model without such a leaf hands the step no counts
    from ray_tpu.models.llama import loss_and_counts
    plain = LlamaConfig.tiny(num_experts=4)
    _, (_, counts) = jax.jit(lambda p: loss_and_counts(
        p, {"tokens": TOKENS}, plain))(init_params(jax.random.PRNGKey(0),
                                                   plain))
    assert counts is None


# -- 1d: the residual's maps ---------------------------------------------------

def test_sinkhorn_rows_and_columns_sum_to_one():
    logits = jnp.clip(3.0 * jax.random.normal(
        jax.random.PRNGKey(0), (4, 4, 500)), -30, 30)
    m = sinkhorn(logits, 20, 1e-6)
    # columns are divided last: 1 to the eps.  Rows are 1 as far as 20
    # rounds converge: to a thousandth for the median token, to a tenth
    # for the worst of 500 drawn three times as wide as the model's start
    np.testing.assert_allclose(jnp.sum(m, axis=0), 1.0, atol=1e-5)
    off = np.abs(np.asarray(jnp.sum(m, axis=1)) - 1.0)
    assert off.max() < 0.1 and np.median(off) < 1e-3
    assert float(jnp.min(m)) >= 0.0
    ours = jnp.moveaxis(m, -1, 0)                  # (T, n, n)
    theirs = xing4.sinkhorn(jnp.moveaxis(logits, -1, 0), 20, 1e-6)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5)


def test_the_maps_start_near_the_plain_residual_and_not_at_it():
    cfg = tiny()
    moe = init_params(jax.random.PRNGKey(0), cfg)["layers"][1]
    bias = np.asarray(moe["hc_ffn_bias"][0])
    res = np.asarray(sinkhorn(jnp.asarray(bias[8:].reshape(4, 4, 1)), 20,
                              1e-6))[..., 0]
    assert np.all(np.diag(res) > 0.85) and np.all(np.diag(res) < 0.99)
    assert not np.allclose(res, 0.25, atol=0.1)    # not the uniform matrix
    np.testing.assert_allclose(1 / (1 + np.exp(-bias[:4])), 0.25, atol=0.06)
    assert np.all(bias != 0.0)     # control.py scales a column by its peak


# -- the traffic, the scopes ---------------------------------------------------

def test_the_sliced_files_traffic_never_leaves_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held) == (
        16384, 64, 8)
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 4,
                              8192)
    assert drawn.shape == (4, 8193) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 16000 < drawn.max() < 16384


def test_the_new_scopes_are_step_scopes_and_open_in_the_program():
    assert {"hc_map", "hc_mix", "mtp_in"} <= set(STEP_SCOPES)
    cfg = tiny()
    text = jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg)[0]).lower(
        init_params(jax.random.PRNGKey(0), cfg)).as_text(debug_info=True)
    for scope in ("hc_map", "hc_mix", "mtp_in", "attn_qkv", "moe_experts",
                  "ffn"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope


# -- the two halves of a block as operations (ops/streams.py) ----------------

HC = dict(norm_eps=1e-6, clamp=(-30.0, 30.0), iters=20, eps=1e-6)


def _plain_maps(xs, proj, scale, bias, n):
    """``_hc_maps`` as it stood before the operations (PR 34): plain sums
    that autodiff differentiates.  ``xs (T, n d)``; tokens minor."""
    x32 = xs.astype(jnp.float32)
    normed = (x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        + HC["norm_eps"])).astype(xs.dtype)
    # the rounded operands widened: the same products and float32 sums as
    # the model's bfloat16 x bfloat16 -> float32, whose gradient the CPU's
    # compiler refuses
    raw = jnp.dot(normed.astype(jnp.float32),
                  proj.astype(xs.dtype).astype(jnp.float32)).T
    raw = (raw * jnp.repeat(scale, np.array([n, n, n * n]))[:, None]
           + bias[:, None])
    res = sinkhorn(jnp.clip(raw[2 * n:].reshape(n, n, -1), *HC["clamp"]),
                   HC["iters"], HC["eps"])
    return jax.nn.sigmoid(raw[:n]), 2.0 * jax.nn.sigmoid(raw[n:2 * n]), res


def _plain_read(xs, proj, scale, bias, n):
    """-> ``x`` and the maps as ``streams.maps_of`` gives them."""
    pre, post, res = _plain_maps(xs, proj, scale, bias, n)
    d = xs.shape[-1] // n
    x = sum(pre[j][:, None] * xs[:, j * d:(j + 1) * d].astype(jnp.float32)
            for j in range(n)).astype(xs.dtype)
    return x, (pre.T, post.T, jnp.moveaxis(res, -1, 0))


def _plain_write(xs, y, post, res, n):
    """``_hc_block``'s second half as it stood; ``post (T, n)``, ``res
    (T, n, n)``."""
    d = xs.shape[-1] // n
    xj = [xs[:, j * d:(j + 1) * d].astype(jnp.float32) for j in range(n)]
    return jnp.concatenate([
        (post[:, i, None] * y.astype(jnp.float32)
         + sum(res[:, i, j, None] * xj[j] for j in range(n))
         ).astype(xs.dtype) for i in range(n)], axis=-1)


def _stream_case(n, d, tokens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    maps = n * (2 + n)
    return dict(
        xs=(draw(tokens, n * d) * (1 + draw(tokens, 1) ** 2)).astype(dtype),
        y=draw(tokens, d).astype(dtype),
        # wide enough for the rounds to matter and the clip to bite nowhere
        proj=draw(n * d, maps) / np.sqrt(n * d) * 2.0,
        scale=jnp.asarray([0.7, 1.3, 1.1], jnp.float32),
        bias=draw(maps) * 0.5,
        w_x=draw(tokens, d), w_out=draw(tokens, n * d),
        w_pre=draw(tokens, n), w_post=draw(tokens, n),
        w_res=draw(tokens, n, n))


def _plan(case, n, form, tile):
    from ray_tpu.ops import streams
    return streams.plan_for(case["xs"], n, form=form, tile=tile, **HC)


STREAM_SHAPES = [
    # n, d, tokens, tile: several tiles; one tile; two streams
    (4, 256, 512, 128), (4, 128, 256, 256), (2, 128, 384, 128),
    (2, 640, 128, 128)]


@pytest.mark.parametrize("form", ["kernels", "xla"])
@pytest.mark.parametrize("n,d,tokens,tile", STREAM_SHAPES)
def test_streams_read_equals_the_plain_sums_and_their_gradients(
        n, d, tokens, tile, form):
    """``x``, the maps, and the gradients to the streams, the projection,
    the three scales and the bias of a weighted sum of ALL its outputs
    (the streams handed on included), against autodiff of the plain form."""
    from ray_tpu.ops import streams
    c = _stream_case(n, d, tokens)
    plan = _plan(c, n, form, tile)
    assert plan.tile == (tile if form == "kernels" else 0)

    def weighed(x, pre, post, res, handed):
        return (jnp.sum(c["w_x"] * x) + jnp.sum(c["w_pre"] * pre)
                + jnp.sum(c["w_post"] * post) + jnp.sum(c["w_res"] * res)
                + jnp.sum(c["w_out"] * handed))

    def ours(xs, proj, scale, bias):
        x, maps, handed = streams.streams_read(plan, xs, proj, scale, bias)
        return weighed(x, *streams.maps_of(maps, n), handed), (x, maps)

    def plain(xs, proj, scale, bias):
        x, maps = _plain_read(xs, proj, scale, bias, n)
        return weighed(x, *maps, xs), (x, maps)

    args = (c["xs"], c["proj"], c["scale"], c["bias"])
    (_, (x, maps)), got = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    (_, (want_x, want_maps)), want = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    np.testing.assert_allclose(x, want_x, rtol=2e-5, atol=2e-5)
    for ours_, theirs in zip(streams.maps_of(maps, n), want_maps):
        np.testing.assert_allclose(ours_, theirs, rtol=2e-5, atol=2e-6)
    # what no map's number lies in stays zero
    assert float(jnp.sum(jnp.abs(maps))) == pytest.approx(float(sum(
        jnp.sum(jnp.abs(m)) for m in want_maps)), rel=1e-5)
    for name, g, w in zip(("xs", "proj", "scale", "bias"), got, want):
        scale_ = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5 * scale_,
                                   err_msg=name)


@pytest.mark.parametrize("form", ["kernels", "xla"])
@pytest.mark.parametrize("n,d,tokens,tile", STREAM_SHAPES)
def test_streams_write_equals_the_plain_sums_and_their_gradients(
        n, d, tokens, tile, form):
    """``X'`` and the gradients to the streams (what goes round the block),
    to the block's output and to the maps."""
    from ray_tpu.ops import streams
    c = _stream_case(n, d, tokens, seed=1)
    plan = _plan(c, n, form, tile)
    _, maps, _ = streams.streams_read(plan, c["xs"], c["proj"], c["scale"],
                                      c["bias"])
    pre, post, res = streams.maps_of(maps, n)

    def ours(xs, y, maps):
        out = streams.streams_write(plan, xs, y, maps)
        return jnp.sum(c["w_out"] * out), out

    def plain(xs, y, post, res):
        out = _plain_write(xs, y, post, res, n)
        return jnp.sum(c["w_out"] * out), out

    (_, out), (dxs, dy, dmaps) = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1, 2), has_aux=True))(c["xs"], c["y"], maps)
    (_, want_out), (want_dxs, want_dy, dpost, dres) = jax.jit(
        jax.value_and_grad(plain, argnums=(0, 1, 2, 3), has_aux=True))(
            c["xs"], c["y"], post, res)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(dxs, want_dxs, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(dy, want_dy, rtol=2e-5, atol=2e-5)
    got_pre, got_post, got_res = streams.maps_of(dmaps, n)
    assert float(jnp.max(jnp.abs(got_pre))) == 0.0
    np.testing.assert_allclose(got_post, dpost, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_res, dres, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("form", ["kernels", "xla"])
def test_a_block_on_bfloat16_streams_stays_beside_the_plain_sums(form):
    """The dtype the model runs: a whole block ``X' = res X + post^T
    f(pre X)`` and its gradients, both halves chained (so the part that
    goes round the block meets the rest where ``dX`` is written), within
    bfloat16's rounding of the plain form."""
    from ray_tpu.ops import streams
    n, d, tokens = 4, 128, 256
    c = _stream_case(n, d, tokens, seed=2, dtype=jnp.bfloat16)
    plan = _plan(c, n, form, None)
    block = lambda x: jnp.tanh(x.astype(jnp.float32) * 0.5).astype(x.dtype)

    def ours(xs, proj, scale, bias):
        x, maps, xs = streams.streams_read(plan, xs, proj, scale, bias)
        out = streams.streams_write(plan, xs, block(x), maps)
        return jnp.sum(c["w_out"] * out.astype(jnp.float32))

    def plain(xs, proj, scale, bias):
        x, (_, post, res) = _plain_read(xs, proj, scale, bias, n)
        out = _plain_write(xs, block(x), post, res, n)
        return jnp.sum(c["w_out"] * out.astype(jnp.float32))

    args = (c["xs"], c["proj"], c["scale"], c["bias"])
    got_v, got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2, 3)))(*args)
    want_v, want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3)))(
        *args)
    assert got[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(got_v, want_v, rtol=2e-2)
    for name, g, w in zip(("xs", "proj", "scale", "bias"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        off = np.sqrt(np.mean(np.square(g - w)) / np.mean(np.square(w)))
        assert off < 3e-2, (name, off)


def test_the_stream_kernels_take_what_tiles_the_chip_and_nothing_else():
    from ray_tpu.ops import streams
    assert streams.kernels_fit(4, 3584, 8192)      # the published block
    assert not streams.kernels_fit(4, 64, 8192)    # half a lane block
    assert not streams.kernels_fit(4, 128, 66)     # no whole tile
    assert not streams.kernels_fit(9, 128, 256)    # a row of res in a group
    xs = jnp.zeros((2, 33, 4 * 64))
    assert streams.plan_for(xs, 4, **HC).tile == 0
    assert streams.plan_for(jnp.zeros((1, 512, 512)), 4, **HC).tile == 256
    assert streams.plan_for(jnp.zeros((3, 128, 512)), 4, **HC).tile == 128
    assert streams.plan_for(jnp.zeros((1, 512, 512)), 4, form="xla",
                            **HC).tile == 0
    with pytest.raises(ValueError, match="stream kernels"):
        streams.plan_for(xs, 4, form="kernels", **HC)
