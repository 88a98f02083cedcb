"""SDAR trained by block diffusion at CPU size, float32: the denoising
objective on the normal path (``models/llama.py``: the corruption, one pass
over ``[noised ; clean]``, the loss of the masked positions' own tokens over
``p``), the mixer ``block_attention`` and the block rule in the flash
kernels (``ops/attention.py``: ``flash_*_bd``) against the plain reference
``benchmark/reference/sdar_block_diffusion.py`` — loss, the noised stream's
logits, every gradient —, the rule against ``mha_reference``'s dense mask,
the two properties that need no oracle, the train step's noise by its step,
the refusals, the counts.  The tiny model is ``tests/tiny_models.py``'s row
``sdar``: blocks of 4 of 64 positions.  Small cases: the kernels run in
interpret mode at L <= 128."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_sdar
from benchmark.reference import sdar_block_diffusion
from ray_tpu.models import llama
from ray_tpu.models.blocks import MIXERS
from ray_tpu.models.blocks.base import Ctx
from ray_tpu.models.llama import LlamaConfig, forward, loss_fn
from ray_tpu.ops import attention
from ray_tpu.ops.layers import repeat_kv_heads
from ray_tpu.train.core import STEP_SCOPES

import tiny_models
from tiny_models import (
    SDAR_NOISE, against_the_reference, program, train_step_reports)

ROW = tiny_models.ROWS["sdar"]
TOKENS = ROW.tokens
X0 = TOKENS[:, :-1]
LENGTH = X0.shape[1]
tiny = functools.partial(tiny_models.tiny, "sdar")
HIGHEST = jax.default_matmul_precision("highest")
BLOCK16 = tuple(sorted({**dict(SDAR_NOISE), "block_length": 16}.items()))


# -- (a) against the plain reference ------------------------------------------

@pytest.mark.parametrize("kw,conf", [
    ({}, None),
    (dict(attn_impl="flash", remat=True), None),
    (dict(attn_impl="flash", block_diffusion=BLOCK16),
     dict(block_diffusion=BLOCK16)),
], ids=["reference", "flash-under-the-checkpoint", "flash-blocks-of-16"])
def test_loss_logits_and_gradients_equal_the_plain_reference(kw, conf):
    """The denoising loss, the load-balancing term, the probe of each row's
    log-softmax, every gradient leaf and the noised stream's logits, by the
    XLA form and by ``flash_*_bd`` (under the layer checkpoint; at a larger
    block); the program masks the positions the reference masks."""
    _, got, want, _ = against_the_reference(
        "sdar", parts=("loss", "aux_loss"), conf=conf, **kw)
    np.testing.assert_allclose(got["bd_masked_share"],
                               want["bd_masked_share"], rtol=1e-6)
    assert 0.0 < float(got["bd_masked_share"]) < 1.0
    assert float(got["bd_mask_off"]) == 0.0
    side = program("sdar", **kw)
    with HIGHEST:
        logits, _ = jax.jit(lambda p: forward(p, X0, side.cfg))(side.params)
        theirs = sdar_block_diffusion.logits(
            side.params, X0, {**ROW.conf, **(conf or {})})
    assert logits.shape == (*X0.shape, side.cfg.vocab_size)
    np.testing.assert_allclose(logits, theirs, atol=5e-5)


def test_the_loss_is_the_masked_positions_own_tokens_over_p_and_no_shift():
    """From ``forward``'s logits and the reference's draws: the sum over the
    MASKED positions of the own token's loss over ``p``, divided by every
    position; the next token's (a shift left in) is another number."""
    side = program("sdar")
    with HIGHEST:
        (_, parts) = side.loss(side.params)
        logits, _ = jax.jit(lambda p: forward(p, X0, side.cfg))(side.params)
    _, m, p = sdar_block_diffusion.corrupt(X0, SDAR_NOISE)
    logp = jax.nn.log_softmax(logits, -1)
    own = -jnp.take_along_axis(logp, X0[..., None], -1)[..., 0]
    nxt = -jnp.take_along_axis(logp, TOKENS[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(parts["loss"], jnp.sum(own * m / p) / own.size,
                               rtol=1e-5)
    assert abs(float(jnp.sum(nxt * m / p) / own.size)
               - float(parts["loss"])) > 0.01


# -- (b) the kernels' rule against the dense mask ------------------------------

def _dense_rule(length, block):
    """The four cases, pair by pair, in numpy."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for r in range(2 * length):
        for c in range(2 * length):
            rb, cb = (r % length) // block, (c % length) // block
            if r >= length:
                seen[r, c] = c >= length and cb <= rb
            else:
                seen[r, c] = cb < rb if c >= length else cb == rb
    return seen


@pytest.mark.parametrize("length,block", [(16, 4), (24, 8), (32, 2)])
def test_the_oracles_mask_is_the_four_cases_and_the_count_its_sum(length,
                                                                  block):
    mask = np.asarray(attention.block_mask(length, block))
    np.testing.assert_array_equal(mask, _dense_rule(length, block))
    np.testing.assert_array_equal(
        attention.block_mask(length, block, length - block, 2 * block),
        mask[length - block:length + block])
    assert mask.sum() == attention.block_needed_pairs(length, block) \
        == flops_sdar.needed_pairs(
            {"block_diffusion": {"block_length": block}}, length) \
        == length * (length + block)
    assert not mask[length:, :length].any()         # clean on noised: never


@pytest.mark.parametrize("length,block,heads,kv_heads,block_q,block_k", [
    (64, 4, 4, 2, 32, 32),      # four tiles a stream, a diagonal in each
    (64, 8, 2, 2, 16, 32),      # q and kv tiles of two sizes
    (128, 16, 2, 1, 64, 32),    # a group of two on one KV head
    (96, 4, 2, 2, 2048, 2048),  # one tile of 96 rows
], ids=["tiles32", "q16-k32", "q64-k32-grouped", "one-tile-of-96"])
def test_the_kernels_equal_the_dense_mask_forward_and_three_gradients(
        length, block, heads, kv_heads, block_q, block_k):
    """``flash_fwd_bd`` / ``flash_dkv_bd`` in interpret mode against
    ``mha_reference`` under ``block_mask``: a block edge lies inside every
    diagonal sub-tile (sub-tiles of 16 to 96 rows, blocks of 4 to 16); dk
    and dv of a clean key sum over the clean AND the noised queries."""
    keys = jax.random.split(jax.random.PRNGKey(length + block), 4)
    d = 16
    q, w = (jax.random.normal(k, (2, 2 * length, heads, d))
            for k in keys[:2])
    k, v = (jax.random.normal(k, (2, 2 * length, kv_heads, d))
            for k in keys[2:])
    tiles = attention.block_tiles(length, block, d, q.dtype, block_q, block_k)
    assert tiles is not None and tiles[2] > block
    assert int(attention.block_schedule_off(length, block, tiles)) == 0

    def ours(q, k, v):
        return jnp.sum(w * attention.flash_attention(
            q, k, v, block=block, block_q=block_q, block_k=block_k))

    def theirs(q, k, v):
        return jnp.sum(w * attention.mha_reference(
            q, *repeat_kv_heads(q, k, v), block=block))

    with HIGHEST:
        got = jax.jit(jax.value_and_grad(ours, (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(theirs, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, a, b in zip("qkv", got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg="d" + name)


def test_a_block_that_divides_no_sub_tile_runs_the_dense_form():
    assert attention.block_tiles(64, 3, 16, jnp.float32) is None
    assert attention.block_tiles(64, 1, 16, jnp.float32) is None
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 2, 16))
    np.testing.assert_allclose(
        attention.flash_attention(q, q, q, block=3),
        attention.mha_reference(q, q, q, block=3), atol=1e-6)
    with pytest.raises(ValueError, match="two streams"):
        attention.flash_attention(q, q, q, block=16)    # 12 positions


def test_the_schedule_runs_neither_the_dead_quadrant_nor_the_off_diagonal():
    """At the cell's size and tiles: executed pairs under 1.5 of the needed
    ``L (L + B)`` (the mask-operand route over the causal 2 L square reads
    over 2), and the strip the step tests agrees with the four cases."""
    length, block = 8192, 4
    tiles = attention.block_tiles(length, block, 128, jnp.bfloat16)
    assert tiles[:2] == (2048, 2048) and tiles[2] % block == 0
    executed = attention.block_tile_counts(length, tiles)["executed_pairs"]
    share = executed / attention.block_needed_pairs(length, block)
    assert 1.0 < share < 1.5
    causal = attention.causal_tile_counts(2 * length, 2 * length, *tiles)
    assert causal["executed_pairs"] / attention.block_needed_pairs(
        length, block) > 2.0
    small = attention.block_tiles(64, 4, 16, jnp.float32, 32, 32)
    assert int(attention.block_schedule_off(64, 4, small)) == 0


def test_a_wrong_threshold_shows_in_the_strip_the_step_tests(monkeypatch):
    """``bd_mask_off`` is the kernels' own walk and test (``_walk_tile``,
    ``_scores``) beside the four cases: a noised row that reads its own
    block from the clean keys (no strict test) is counted."""
    tiles = attention.block_tiles(64, 4, 16, jnp.float32, 32, 32)
    scores = attention._scores

    def lenient(q, k, mask, transposed=False, rule=None):
        return scores(q, k, mask, transposed, rule and (rule[0], 0))

    monkeypatch.setattr(attention, "_scores", lenient)
    # 32 rows of the noised strip each see their own block of 4 clean keys
    assert int(attention.block_schedule_off(64, 4, tiles)) == 32 * 4


# -- (c) the properties that need no oracle ------------------------------------

@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_what_a_changed_token_can_move(impl):
    """Through the whole model (both streams' logits): a changed NOISED
    token of block b moves no logit outside block b of the noised stream
    and nothing of the clean stream; a changed CLEAN token of block b moves
    noised logits only in blocks AFTER b (and clean ones from b on)."""
    cfg = tiny(attn_impl=impl)
    params = program("sdar").params
    block = cfg.bd_block
    streams = jnp.concatenate([jnp.full_like(X0, 127), X0], axis=1)[:1]

    @jax.jit
    def logits(streams):
        h, _, _ = llama._hidden(params, streams, cfg, None, None)
        return llama._lm_head(params, h, cfg, lambda x, _: x)[0]

    def moved(at):
        changed = streams.at[0, at].set((streams[0, at] + 1) % 127)
        delta = jnp.max(jnp.abs(logits(changed) - logits(streams)), axis=-1)
        return np.asarray(delta[:LENGTH]), np.asarray(delta[LENGTH:])

    with HIGHEST:
        b = 5
        at = b * block + 1
        noised, clean = moved(at)                       # a noised token
        inside = np.arange(LENGTH) // block == b
        assert noised[inside].min() > 1e-4
        assert noised[~inside].max() < 1e-6 and clean.max() < 1e-6
        noised, clean = moved(LENGTH + at)              # its clean copy
        after = np.arange(LENGTH) // block > b
        assert noised[after].min() > 1e-5 and noised[~after].max() < 1e-6
        from_b = np.arange(LENGTH) // block >= b
        assert clean[from_b].min() > 1e-5 and clean[~from_b].max() < 1e-6


# -- (d) the train step's noise -------------------------------------------------

def test_the_train_step_draws_by_its_step_and_learns():
    """Step 0's noise is ``loss_fn``'s; another step draws other noise, the
    same step the same again (a resumed job repeats its draws); the step
    runs the block rule's kernels under the scope ``attention``, the noise
    under ``bd_noise``, and learns."""
    assert "bd_noise" in STEP_SCOPES
    stepped = train_step_reports("sdar")
    cfg, state, compiled = stepped.cfg, stepped.initial, stepped.compiled
    at = lambda n: dataclasses.replace(  # noqa: E731
        state, step=jnp.asarray(n, jnp.int32))
    batch = {"tokens": TOKENS}
    first, again, other = (compiled(at(n), batch)[1] for n in (0, 0, 3))
    _, at_rest = jax.jit(lambda p: loss_fn(p, batch, cfg))(state.params)
    for name in ("loss", "bd_masked_share"):
        assert float(first[name]) == float(again[name])
        np.testing.assert_allclose(first[name], at_rest[name], rtol=1e-5)
    assert float(other["bd_masked_share"]) != float(first["bd_masked_share"])
    _, m0, _ = sdar_block_diffusion.corrupt(X0, SDAR_NOISE, 0)
    _, m3, _ = sdar_block_diffusion.corrupt(X0, SDAR_NOISE, 3)
    assert float(first["bd_masked_share"]) == pytest.approx(float(m0.mean()))
    assert float(other["bd_masked_share"]) == pytest.approx(float(m3.mean()))
    assert set(sdar_block_diffusion.STEP_METRICS) <= set(first)
    assert float(first["bd_mask_off"]) == 0.0
    assert float(first["moe_dropped"]) == 0.0
    assert int(stepped.state.step) == 3


# -- (e) what is not built refuses by message ------------------------------------

@pytest.mark.parametrize("fields,said", [
    (dict(attn_impl="ring"), "attn_impl 'ring'"),
    (dict(attn_impl="ulysses"), "attn_impl 'ulysses'"),
    (dict(num_nextn=1), "num_nextn"),
    (dict(sliding_window=16), "sliding window"),
    (dict(layer_types=("sliding_attention",) * 2, sliding_window=16),
     "sliding window"),
    (dict(sa_config=tiny_models.KEYE_INDEXER), "indexer"),
    (dict(block_diffusion=(("block_length", 4),)), "mask_token_id"),
    (dict(block_diffusion=tuple(sorted(
        {**dict(SDAR_NOISE), "mask_token_id": 128}.items()))),
     "below vocab_size"),
], ids=lambda x: None if isinstance(x, str) else "-".join(x))
def test_a_configuration_that_is_not_built_is_refused(fields, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        tiny(**fields)


def test_the_paths_that_are_not_built_are_refused():
    cfg = tiny()
    params = program("sdar").params
    with pytest.raises(NotImplementedError, match="pipelined"):
        loss_fn(params, {"tokens": TOKENS}, cfg,
                forward_fn=lambda p, t: forward(p, t, cfg))
    with pytest.raises(NotImplementedError, match="pipelined=False"):
        llama._one_kind(cfg, "the pipelined path")
    with pytest.raises(NotImplementedError, match="manual over 'sp'"):
        MIXERS["block_attention"].apply(
            Ctx(cfg, None, lambda x, _: x, True),
            jnp.zeros((1, 2 * LENGTH, cfg.embed_dim)), {}, {})
    # and never silently next-token: every layer is the block rule's mixer
    assert cfg.layer_kinds == (("block_attention", "moe"),) * 2
    assert LlamaConfig.tiny().bd_block == 0
