"""Keye-VL-2.0-30B-A3B's language model at CPU size, float32: softmax
attention over the keys a learned indexer picks (``ops/sparse_attention.py``,
the mixer ``indexed``) against the plain reference
``benchmark/reference/keye_sparse.py`` — loss, the indexer's own loss, each
token's loss and every gradient; which loss reaches which parameter; the
selection's rule of ties and its exact size; a selection of everything
against the dense mixer; the kernels against their XLA forms on several
tiles; the eight shares of the expert layer; a mesh; the train step; the
configuration file.  The tiny model is ``tests/tiny_models.py``'s row
``keye``: 16 keys a query of 128."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import keye_sparse, mellum
from ray_tpu.models import llama
from ray_tpu.models.blocks import attention as attention_block
from ray_tpu.models.llama import loss_fn
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.moe import moe_block
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

import tiny_models
from tiny_models import (
    against_the_reference, fault_ids, program, shares_add_up, side_of,
    stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "keye-vl-2.0-30b-a3b-1of8"
ROW = tiny_models.ROWS["keye"]
TOKENS = ROW.tokens
tiny = functools.partial(tiny_models.tiny, "keye")
HIGHEST = jax.default_matmul_precision("highest")
INDEXER = ("wq_idx", "wk_idx", "w_idx", "k_idx_norm", "k_idx_bias")


# -- (a) against the plain reference ------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "flash-under-the-checkpoint"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """The XLA forms, and the kernels (``sparse_scores``, ``sparse_select``,
    ``flash_*_dsa``; the KL in XLA at a head of 16) under the layer
    checkpoint: total, next-token loss, the indexers' loss, each token's loss
    and every gradient leaf; the selection holds exactly ``topk`` keys a
    query, and the share the program counts is the reference's."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    _, got, want, _ = against_the_reference(
        "keye", parts=("loss", "idx_loss"), **kw)
    assert float(got["idx_loss"]) > 0.05        # a loss that is there
    assert float(got["dsa_selected_off"]) == 0.0
    np.testing.assert_allclose(got["dsa_selected_share"],
                               want["dsa_selected_share"], rtol=1e-6)
    s, k = 128, 16
    assert float(got["dsa_selected_share"]) == pytest.approx(
        (k * (k + 1) // 2 + (s - k) * k) / (s * (s + 1) // 2), rel=1e-6)
    # the kernel's first block of four holds the rows of fewer than 16 keys,
    # which walk at a width of 128, and where every head's ReLU is shut
    # scores tie at 0.0; ``lax.top_k`` walks nothing
    walked = float(got["dsa_tie_walk_share"])
    assert walked == 0.0 if impl == "reference" else 0.25 <= walked <= 1.0


def test_each_loss_reaches_its_own_parameters_and_no_other():
    """The indexer's parameters have ZERO gradient from the next-token loss
    (the selection passes none) and one from their own loss; the stream has
    none from the indexers' loss: every other gradient is the same with the
    loss in or out."""
    side = program("keye")
    (_, parts), with_idx = side.value_and_grad(side.params)
    alone = side_of("keye", tiny(idx_loss_coef=0.0), side.params)
    (total, _), without = alone.value_and_grad(side.params)
    np.testing.assert_allclose(total, parts["loss"], rtol=1e-6)
    for name, g in without["layers"].items():
        if name in INDEXER:
            assert float(jnp.max(jnp.abs(g))) == 0.0, name
            assert float(jnp.max(jnp.abs(with_idx["layers"][name]))) > 1e-4, \
                name
        else:
            np.testing.assert_allclose(g, with_idx["layers"][name],
                                       atol=1e-7, err_msg=name)
    for name in ("embed", "lm_head", "final_norm"):
        np.testing.assert_allclose(without[name], with_idx[name], atol=1e-7)


@pytest.mark.parametrize("fault", fault_ids("keye"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """What the chip's check is asked to see (the configuration's
    ``check.why``; the row's ``faults``), in float32 where nothing hides
    it."""
    stands_apart("keye", fault)


# -- (b) the selection --------------------------------------------------------

def _scores(b=2, s=256, ties=True, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, s))
    if ties:    # a quarter apart: dozens of equal scores a row, 0.0 and -0.0
        x = jnp.round(x * 4) / 4
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return jnp.where(causal, x + 0.0, sa.NEG_INF), causal


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("ties", [True, False])
def test_the_selection_is_top_ks_own_ties_to_the_lower_key(kernel, ties):
    """Exactly ``topk`` keys a row (every causal key before), and among
    equal scores at the threshold the LOWER keys: the set ``lax.top_k``
    takes, which breaks ties so — by ``lax.top_k`` itself and by the kernel
    that counts bits and sorts nothing."""
    b, s, topk = 2, 256, 40
    scores, causal = _scores(b, s, ties)
    mask = sa.selection(scores, *sa.select(scores, topk, kernels=kernel)[:2])
    _, keys = jax.lax.top_k(scores, topk)
    want = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        keys].set(True) & causal
    np.testing.assert_array_equal(mask != 0, want)
    rows = np.minimum(np.arange(s) + 1, topk)
    np.testing.assert_array_equal(np.asarray(mask).sum(-1),
                                  np.broadcast_to(rows, (b, s)))
    if ties:    # the rule did bind: somewhere an equal score was left out
        tau = jnp.take_along_axis(scores, keys[..., -1:], -1)
        assert int(jnp.sum((scores == tau) & causal & (mask == 0))) > 0
    np.testing.assert_array_equal(sa.selected_pairs(mask),
                                  [rows.sum()] * b)


def _both_selects(scores, topk):
    """``sparse_select`` (interpret mode) and ``lax.top_k`` on the same
    scores: ``((tau, tie, walked), (tau, tie))``."""
    got = jax.jit(functools.partial(sa.select, topk=topk))(scores)
    return got, sa.select(scores, topk, kernels=False)[:2]


@pytest.mark.parametrize("s,topk,ties", [
    (640, 100, False),      # 5 chunks of 128 columns; topk under a chunk
    (640, 128, False),      # a chunk exactly: no short row walks
    (640, 200, False),      # over a chunk: the least width is two
    (640, 100, True),       # ties at tau in every chunk a row can see
    (640, 200, True),
    (256, 40, True),        # one chunk; short rows across block 0's edge
    (1536, 700, True),      # chunks of 512; 22 blocks of short rows
    (4096, 2048, False),    # the cell's chunk, 2048, and topk = the chunk
    (4096, 512, True),
], ids=["under-a-chunk", "a-chunk", "over-a-chunk", "ties-under", "ties-over",
        "one-chunk", "chunks-of-512", "the-cells-chunk", "the-cells-chunk-ties"])
def test_the_selection_kernel_is_top_ks_two_numbers_in_every_row(
        s, topk, ties):
    """``tau`` AND ``tie`` of the kernel equal ``lax.top_k``'s in every row,
    not only the mask they make — over several chunks of counted columns
    (``sa._select_chunk``: 128 at 640 keys, 2048 at 4096) with ``topk``
    under, at and over a chunk's width; rows of fewer than ``topk`` keys in
    the first block, across its edge and across a chunk's (they read
    ``NEG_INF`` and ``topk - 1``, the ``NEG_INF`` columns counted up to the
    least width); with ties, a row whose keys AT ``tau`` straddle a chunk's
    edge, admitted on one side and left out on the other."""
    scores, causal = _scores(1, s, ties, seed=s + topk)
    (tau, tie, walked), (want_tau, want_tie) = _both_selects(scores, topk)
    np.testing.assert_array_equal(tau, want_tau)
    np.testing.assert_array_equal(tie, want_tie)
    short = np.arange(s) + 1 < topk
    assert (np.asarray(tau)[0, short] == sa.NEG_INF).all()
    assert (np.asarray(tie)[0, short] == topk - 1).all()
    chunk = sa._select_chunk(s)
    assert s // chunk > 1 or s == 256
    if ties and s // chunk > 1:
        at_tau = np.asarray((scores == tau[..., None]) & causal)[0]
        keys, last = np.arange(s), np.asarray(tie)[0][:, None]
        assert any(
            ((at_tau & (keys < edge) & (keys <= last)).any(-1)
             & (at_tau & (keys >= edge) & (keys > last)).any(-1)
             & ~short).any() for edge in range(chunk, s, chunk))
        assert float(walked[0]) > 0.5
    if not ties and topk % chunk == 0:
        assert float(walked[0]) == 0.0      # nothing binds, nothing walks


def test_a_tie_is_walked_only_in_the_block_where_it_binds():
    """The counter, and both paths on the same block.  Tie-free scores at
    ``topk`` = a chunk: no block walks (``walked`` 0.0) and every row's
    ``tie`` is the one pass's.  ONE score copied over a lower one in ONE
    row, so that two keys sit at that row's ``tau`` and one fits: its block
    of 32 rows walks and no other (1 of 20), the row's numbers are
    ``lax.top_k``'s, and the thirty-one rows beside it — whose tie does not
    bind — read from the walk the same two numbers the one pass gave them.
    On ``_scores(ties=True)`` nearly every block walks."""
    s, topk, row = 640, 128, 403
    scores, _ = _scores(1, s, ties=False, seed=7)
    (tau0, tie0, walked0), want0 = _both_selects(scores, topk)
    assert float(walked0[0]) == 0.0
    np.testing.assert_array_equal(tau0, want0[0])
    np.testing.assert_array_equal(tie0, want0[1])
    # a second key at the row's tau, in another chunk, where a lower stood
    lower = np.flatnonzero(np.asarray(scores[0, row, :row]) < tau0[0, row])
    to = int(lower[lower // 128 != int(tie0[0, row]) // 128][0])
    tied = scores.at[0, row, to].set(tau0[0, row])
    (tau1, tie1, walked1), want1 = _both_selects(tied, topk)
    assert float(walked1[0]) == pytest.approx(1 / (s // sa.SELECT_ROWS))
    np.testing.assert_array_equal(tau1, want1[0])
    np.testing.assert_array_equal(tie1, want1[1])
    assert float(tau1[0, row]) == float(tau0[0, row])
    assert int(tie1[0, row]) == min(to, int(tie0[0, row]))  # the lower key
    others = np.arange(s) != row
    np.testing.assert_array_equal(np.asarray(tau1)[0, others],
                                  np.asarray(tau0)[0, others])
    np.testing.assert_array_equal(np.asarray(tie1)[0, others],
                                  np.asarray(tie0)[0, others])
    often = _both_selects(_scores(1, s, ties=True)[0], topk)[0][2]
    assert float(often[0]) > 0.5


def test_a_selection_of_everything_is_the_dense_mixer():
    """``topk >= s``: nothing is selected away, and the model's next-token
    loss and every token's are the plain softmax mixer's on the same
    tensors (Mellum2's full layer at this model's sizes)."""
    params = program("keye").params
    everything = side_of("keye", tiny(sa_config=tiny_models.Frozen(
        tiny_models.KEYE_INDEXER, topk=128)), params)
    dense_params = dict(params, layers={
        k: v for k, v in params["layers"].items() if k not in INDEXER})
    dense = side_of("keye", tiny(sa_config=None), dense_params)
    _, got = everything.loss(params)
    _, want = dense.loss(dense_params)
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=2e-6)
    np.testing.assert_allclose(everything.token_nll(params),
                               dense.token_nll(dense_params), atol=3e-5)
    assert float(got["dsa_selected_share"]) == 1.0
    assert float(got["dsa_selected_off"]) == 0.0
    # an indexed layer's statistic, 0.0 where no kernel selects; no other's
    assert float(got["dsa_tie_walk_share"]) == 0.0
    assert not [k for k in want if k.startswith("dsa_")]


# -- (c) the kernels against their XLA forms, several tiles -------------------

def _operands(b=1, s=1024, h=4, hk=2, d=128, heads=4, di=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (b, s, h, d)), n(ks[1], (b, s, hk, d)),
            n(ks[2], (b, s, hk, d)), n(ks[3], (b, s, heads, di)),
            n(ks[4], (b, s, di)), n(ks[5], (b, s, heads)))


def test_the_kernels_are_their_xla_forms_over_several_tiles():
    """1024 tokens: 2 x 2 tiles of the index kernels, 4 x 2 of the loss's,
    32 blocks of the selection's; heads of 128 lanes read in place, a group
    of two.  Values and every gradient."""
    operands = _operands()
    topk = 96

    def run(kernels):
        def f(q, k, v, q_idx, k_idx, w):
            scores = sa.index_scores(q_idx, k_idx, w, kernels=kernels)
            sel = sa.selection(scores, *sa.select(scores, topk,
                                                  kernels=kernels)[:2])
            o, lse2 = sa.attend(q, k, v, sel, sm_scale=128 ** -0.5,
                                flash=kernels)
            kl = jnp.sum(attention_block._indexer_loss(
                128 ** -0.5, kernels, False, q_idx, k_idx, w,
                *jax.lax.stop_gradient((scores, sel, q, k, lse2, None)))
            ) / sel.shape[1]
            return 0.01 * jnp.sum(o * o) + kl, (kl, sa.selected_pairs(sel))
        return jax.jit(jax.value_and_grad(
            f, argnums=tuple(range(6)), has_aux=True))(*operands)

    with HIGHEST:
        (want, (want_kl, want_pairs)), want_grads = run(False)
        (got, (got_kl, got_pairs)), got_grads = run(True)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_kl, want_kl, rtol=1e-5)
    np.testing.assert_array_equal(got_pairs, want_pairs)
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b)))



def _locs(text):
    """The locations a lowered text names."""
    return re.findall(r'loc\("([^"]*)"', text)


def _late(locs):
    """Those of ``locs`` that the backward pass runs: the rematerialised
    forward's, the transposed ones and, in the layer scan's backward body,
    those under the layer's ``checkpoint``."""
    return [n for n in locs
            if "rematted_computation" in n or "transpose(" in n
            or n.startswith("checkpoint/")]


@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "under-the-checkpoint"])
def test_the_loss_carries_its_gradient_out_of_the_forward_pass(checkpointed):
    """The layer's rule ``_indexer_loss`` (``kl_and_gradient`` ->
    ``index_grads`` in its forward rule, the cotangent times each in its
    backward) by the kernels (``sparse_loss`` at a head of 128,
    ``sparse_scores_bwd``; interpret mode) against the plain form under
    autodiff — ``indexer_kl``'s
    rows summed, differentiated through ``index_scores``' XLA form — for a
    cotangent that is NOT one and another in each of two sequences: the
    value a sequence and the gradients to q_idx, k_idx (which sums over the
    queries) and w.  Under ``jax.checkpoint`` with the model's policy the
    same numbers, and the backward pass holds neither kernel: both run once,
    in the forward pass, and their three float32 outputs are what is kept."""
    q, k, v, q_idx, k_idx, w = _operands(b=2, s=512)
    topk, scale = 96, 128 ** -0.5
    ct = jnp.asarray([0.37, -1.9])

    def plain(q_idx, k_idx, w):
        scores = sa.index_scores(q_idx, k_idx, w, kernels=False)
        sel = sa.selection(scores, *sa.select(scores, topk,
                                              kernels=False)[:2])
        _, lse2 = sa.attend(q, k, v, sel, sm_scale=scale, flash=False)
        kl = jnp.sum(sa.indexer_kl(scores, sel, q, k, lse2, sm_scale=scale),
                     axis=1)
        return jnp.sum(ct * kl), kl

    def carried(q_idx, k_idx, w):
        scores = sa.index_scores(q_idx, k_idx, w)
        sel, sel_t, lse_i, _ = sa.masks(scores, *sa.select(scores, topk)[:2])
        _, lse2 = sa.attend(q, k, v, sel, sel_t, sm_scale=scale)
        kl = attention_block._indexer_loss(
            scale, True, False, q_idx, k_idx, w,
            *jax.lax.stop_gradient((scores, sel, q, k, lse2, lse_i)))
        return jnp.sum(ct * kl), kl

    if checkpointed:
        carried = jax.checkpoint(
            carried, policy=jax.checkpoint_policies.save_only_these_names(
                *llama._saved_names()))
    grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        f, argnums=(0, 1, 2), has_aux=True))
    # the indexer's operands in the model's type: the cast is the rule's
    low = (q_idx.astype(jnp.bfloat16), k_idx.astype(jnp.bfloat16), w)
    with HIGHEST:
        (_, want_kl), want = grad(plain)(q_idx, k_idx, w)
        (_, got_kl), got = grad(carried)(q_idx, k_idx, w)
        _, got_low = grad(carried)(*low)
    assert got_kl.shape == (2,)
    np.testing.assert_allclose(got_kl, want_kl, rtol=1e-5)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b)))
    assert [x.dtype for x in got_low] == [x.dtype for x in low]
    unit = jax.eval_shape(sa.index_grads, *low, jax.ShapeDtypeStruct(
        (2, 512, 512), jnp.float32))
    assert {x.dtype for x in unit} == {jnp.dtype(jnp.float32)}
    text = grad(carried).lower(q_idx, k_idx, w).as_text(debug_info=True)
    late = _late(_locs(text))
    for kernel in ("sparse_loss", "sparse_scores_bwd"):
        assert kernel in text
        assert not [n for n in late if kernel in n], kernel


@pytest.mark.parametrize("b,s,topk,levels", [
    (1, 1024, 96, None),    # 2 x 2 tiles, tile (0, 1) wholly past the diagonal
    (2, 1024, 96, 7),       # seven values: ties at tau on both sides of tie
    (1, 1536, 700, 5),      # 3 x 3; rows of fewer than topk keys past a tile
    (1, 1024, 1024, 7),     # topk >= s: nothing is selected away
    (1, 1024, 4096, None),
    (1, 384, 64, 3),        # one tile, smaller than INDEX_TILE
], ids=["plain", "ties", "short-rows", "topk-is-s", "topk-over-s",
        "one-tile"])
def test_sparse_mask_is_the_xla_forms(b, s, topk, levels):
    """The kernel ``sparse_mask`` (interpret mode) against what the layer
    made in XLA before it: ``selection``, its ``swapaxes``, ``logsumexp``
    over the selected scores and ``selected_pairs`` — the masks bit for
    bit (zeros past the diagonal, whole dead tiles among them), the counts
    exactly, the log-sum-exp to the order of a float32 sum."""
    scores = jax.random.normal(jax.random.PRNGKey(s + topk), (b, s, s))
    if levels:
        # + 0.0: never -0.0, as the index scores are not
        scores = jnp.round(scores * (levels / 4)) * 0.5 + 0.0
    keys = jnp.arange(s)
    scores = jnp.where(keys[:, None] >= keys, scores, sa.NEG_INF)
    tau, tie, _ = sa.select(scores, topk, kernels=False)
    want = sa.selection(scores, tau, tie)
    if levels and topk < s:
        at_tau = np.asarray(scores == tau[..., None])
        assert (at_tau & np.asarray(keys > tie[..., None])).any()
        assert (at_tau & np.asarray(keys < tie[..., None])).any()
    assert sa.masks(scores, tau, tie, kernels=False)[1:3] == (None, None)
    sel, sel_t, lse_i, pairs = jax.jit(sa.masks)(scores, tau, tie)
    np.testing.assert_array_equal(sel, want)
    np.testing.assert_array_equal(sel_t, jnp.swapaxes(want, 1, 2))
    assert sel.dtype == sel_t.dtype == jnp.int8
    np.testing.assert_array_equal(pairs, sa.selected_pairs(want))
    rows = min(topk, s)
    assert int(pairs[0]) == rows * (rows + 1) // 2 + (s - rows) * rows
    np.testing.assert_allclose(lse_i, jax.nn.logsumexp(
        jnp.where(want != 0, scores, sa.NEG_INF), axis=-1), rtol=1e-6)


# -- (d) the shares -----------------------------------------------------------

def _expert_layer(seed=5, tokens=96, d=32, m=16, experts=64):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa
    return {"x": n(tokens, d), "mlp_norm": 1.0 + 0.1 * n(d),
            "router": n(d, experts) * d ** -0.5,
            "w_gate": n(experts, d, m) * d ** -0.5,
            "w_up": n(experts, d, m) * d ** -0.5,
            "w_down": n(experts, m, d) * m ** -0.5}


@functools.partial(jax.jit, static_argnums=(2,))
def _share(p, first, held):
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=8, norm_eps=1e-6, norm_topk_prob=True,
        scoring="softmax", first_expert=first, residual=False)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """8 chips with 8 of 64 experts each (the file's 8 chips a layer), no
    shared expert: their parts, summed, are the whole layer as the reference
    has it.  Attention and the indexer are whole on every chip (data
    parallel): counted ONCE, they are the reference's own mixer, which test
    (a) holds; nothing of them is divided."""
    p = _expert_layer()
    h = mellum.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    whole, chosen, balance = keye_sparse.expert_ffn(
        h[None], p, k=8, renormalise=True, first=0)
    parts = shares_add_up("keye", p, _share, whole[0], chosen, k=8)
    alone, _, _ = keye_sparse.expert_ffn(
        h[None], {**p, **{w: p[w][16:24] for w in ("w_gate", "w_up",
                                                   "w_down")}},
        k=8, renormalise=True, first=16)
    np.testing.assert_allclose(parts[2][0], alone[0], atol=2e-5)


# -- (e) a mesh, the train step, the configuration file -----------------------

@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_on_a_mesh_the_model_is_one_devices(impl):
    """fsdp=2 x tp=2: scores, selection, attention and the indexer's loss
    run per shard of the batch (whole over ``tp``), in XLA and by the
    kernels (interpreted) inside the manual region; the loss, the indexers'
    and the counters equal one device's."""
    cfg = tiny(attn_impl=impl)
    params = program("keye").params
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    with HIGHEST:
        want, want_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg))(params)
        got, got_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg, mesh=mesh))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    np.testing.assert_allclose(got_m["idx_loss"], want_m["idx_loss"],
                               rtol=1e-5)
    assert float(got_m["dsa_selected_off"]) == 0.0
    np.testing.assert_allclose(got_m["dsa_selected_share"],
                               want_m["dsa_selected_share"], rtol=1e-6)
    assert float(got_m["dsa_tie_walk_share"]) == float(
        want_m["dsa_tie_walk_share"])
    assert (float(got_m["dsa_tie_walk_share"]) > 0.0) == (impl == "flash")


def test_the_train_step_runs_the_kernels_under_their_scopes_and_learns():
    stepped = train_step_reports("keye")
    # under the checkpoint the backward pass selects nothing again and
    # runs no second forward kernel: the selection's two numbers a row
    # and the kernel's output are kept
    locs = _locs(stepped.text)
    again = [n for n in locs if "rematted_computation" in n]
    assert any("sparse_scores" in n for n in again)     # remade, as q and k
    assert any("sparse_mask" in n for n in again)   # from the kept tau, tie
    assert not any("sparse_select" in n or "flash_fwd_dsa" in n
                   for n in again)
    # the indexer's loss hands its backward three unit gradients and no
    # more: ``sparse_scores_bwd`` runs in the FORWARD pass, under its scope,
    # and the KL (in XLA at a head of 16) with its ``(s, s)`` gradient is
    # made once — the rematerialised pass holds nothing of ``dsa_loss``,
    # the backward pass three multiplies and no ``(s, s)`` array
    late = _late(locs)
    assert any(n.startswith("dsa_index/sparse_scores_bwd") for n in locs)
    assert not [n for n in late if "sparse_scores_bwd" in n]
    assert any(n.startswith("dsa_loss/exp") for n in locs)
    assert {n.rsplit("/", 1)[-1] for n in late if "dsa_loss/" in n} == {
        "mul", "div", "broadcast_in_dim", "reshape"}
    metrics = stepped.metrics
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["dsa_selected_off"]) == 0.0
    assert 0.25 <= float(metrics["dsa_tie_walk_share"]) <= 1.0
    assert np.isfinite(float(metrics["idx_loss"]))


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                18992, 128, 16, 0, 0, 8)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.num_selected, cfg.norm_topk_prob,
            cfg.router_scoring, cfg.shared_experts, cfg.select_bias,
            cfg.tie_embeddings, cfg.norm_eps, cfg.rope_theta) == (
                2048, 32, 4, 128, 768, 8, True, "softmax", 0, False, False,
                1e-6, 10000000)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.idx_loss_coef, cfg.qk_head_norm, cfg.qk_norm,
            cfg.aux_loss_coef, cfg.sliding_window) == (
                16, 64, 2048, 1.0, True, False, 0.0, 0)
    assert cfg.kind_runs == ((("indexed", "moe"), 8),)
    kw = keye_sparse.layer_kwargs(conf)
    assert (kw["heads"], kw["kv_heads"], kw["index_heads"], kw["topk"],
            kw["k"], kw["first"]) == (32, 4, 16, 2048, 8, 0)
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              16384)
    assert drawn.shape == (1, 16385) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 18000 < drawn.max() < 18992
