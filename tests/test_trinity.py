"""Trinity-Large-Preview's mechanisms at CPU size in float32: a window in
three attention layers of four (rotary positions in those alone), an output
gate on the attention, four norms a layer, sigmoid-scored experts with a
selection bias beside a shared expert behind one leading dense layer, an
untied head over a vocabulary slice and a held share — the program
(``ray_tpu/models/llama.py`` and its blocks) against the benchmark's plain
reference (``benchmark/reference/afmoe.py``: nothing shared with the code
under test) on seeded weights."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import afmoe
from ray_tpu.models.blocks import MIXERS, attention as attention_block
from ray_tpu.models.llama import init_params, loss_and_counts
from ray_tpu.ops.attention import causal_tile_counts, choose_tiles
from ray_tpu.ops.moe import moe_block
import tiny_models
from tiny_models import (
    F, ROWS, S, TRINITY_WINDOW, against_the_reference, fault_ids, program,
    shares_add_up, stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "trinity-large-preview-1of32"
CONF, TOKENS = ROWS["trinity"].conf, ROWS["trinity"].tokens
WINDOW, SEQ = TRINITY_WINDOW, TOKENS.shape[1] - 1
tiny = functools.partial(tiny_models.tiny, "trinity")


# -- the model against the reference -------------------------------------------

def test_four_kinds_of_layer_hold_only_their_tensors():
    cfg = tiny()
    assert cfg.kind_runs == (((S, "dense"), 1), ((S, "moe"), 2),
                             ((F, "moe"), 1), ((S, "moe"), 1))
    assert afmoe.kinds(CONF) == cfg.layer_kinds
    layers = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    mixer = ["attn_norm", "attn_post_norm", "wq", "wk", "wv", "wo", "q_norm",
             "k_norm", "wg"]
    assert list(layers[0]) == mixer + [
        "mlp_norm", "mlp_post_norm", "w_gate", "w_up", "w_down"]
    for run, n in ((1, 2), (2, 1), (3, 1)):
        assert list(layers[run]) == mixer + [
            "mlp_norm", "mlp_post_norm", "router", "w_gate", "w_up",
            "w_down", "router_bias", "shared_gate", "shared_up",
            "shared_down"]
        assert layers[run]["w_gate"].shape == (n, 4, 64, 32)
        assert layers[run]["router"].shape == (n, 64, 16)
        assert layers[run]["wg"].shape == (n, 64, 64)
        assert layers[run]["q_norm"].shape == (n, 16)
    # the scaled embedding starts at unit RMS, the second norms below 1
    embed = init_params(jax.random.PRNGKey(0), cfg)["embed"]
    assert float(jnp.std(embed)) * 8.0 == pytest.approx(1.0, abs=0.05)
    for run in layers:
        assert np.all(np.asarray(run["attn_norm"]) == 1.0)
        assert np.all(np.asarray(run["mlp_post_norm"]) == 0.25)


@pytest.mark.parametrize("impl", ["reference", "flash-under-the-checkpoint"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """Tolerances: both sides are float32, the program at XLA's default
    matmul precision on the CPU (float32) and the reference at "highest";
    what is left is the order of sums — 2e-5 relative on means of 96
    tokens, 3e-5 nats on one token's loss, 1e-4 of a gradient's largest
    entry (the selection is discrete: a swapped expert would read 1e-2 and
    more).  Once with the XLA attention, once with the windowed flash
    kernels (interpreted) under the layer checkpoint, the chip's path."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    _, parts, want, ours = against_the_reference("trinity", **kw)
    np.testing.assert_allclose(parts["moe_held_share"],
                               want["moe_held_share"], rtol=1e-6)
    assert 0.1 < float(parts["moe_held_share"]) < 0.5
    assert float(parts["moe_dropped"]) == 0.0
    assert len(want["experts"]) == 4
    # every tensor of every kind of layer has a gradient but the selection
    # bias, which no gradient reaches
    for run in ours["layers"]:
        for name, g in run.items():
            assert np.any(np.asarray(g)) == (name != "router_bias"), name


@pytest.mark.parametrize("fault", fault_ids("trinity"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """Each structural point of the configuration, got wrong in the
    program (the row's ``faults``), moves a token's loss by more than a
    thousandth of a nat (the sound program stands 3e-5 off at most): the
    position signal flipped in EITHER kind of layer, the window dropped or
    a key short, the gate, the second norms, the per-head norm, muP's
    factor, the selection bias, ``route_scale``, the shared expert."""
    stands_apart("trinity", fault)


def test_rotary_positions_follow_the_kind_of_layer():
    """The rule is stated once: ``LlamaConfig.rotary``.  A windowed layer
    alone moves when the sequence is shifted under it... seen directly: with
    the same tensors, a full layer's output at a position does not depend
    on where the sequence starts, a windowed layer's q and k do."""
    cfg = tiny()
    assert (cfg.rotary(True), cfg.rotary(False)) == (True, False)
    for name, flags in (("rope", (True, True)), ("nope", (False, False))):
        other = dataclasses.replace(cfg, position_embedding=name)
        assert (other.rotary(True), other.rotary(False)) == flags
    with pytest.raises(ValueError):
        tiny(position_embedding="alibi")
    with pytest.raises(ValueError):
        tiny(sliding_window=0)
    assert MIXERS[S] is attention_block.SLIDING
    assert MIXERS[F] is attention_block.SOFTMAX is MIXERS["attention"]


# -- the shares ----------------------------------------------------------------

def _expert_layer(seed=3, tokens=96, d=32, m=16, experts=32):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa
    return {"x": n(tokens, d), "mlp_norm": 1.0 + 0.1 * n(d),
            "mlp_post_norm": 1.0 + 0.1 * n(d),
            "router": n(d, experts) * d ** -0.5,
            "router_bias": 0.02 * n(experts),
            "w_gate": n(experts, d, m) * d ** -0.5,
            "w_up": n(experts, d, m) * d ** -0.5,
            "w_down": n(experts, m, d) * m ** -0.5,
            "shared_gate": n(d, m) * d ** -0.5,
            "shared_up": n(d, m) * d ** -0.5,
            "shared_down": n(m, d) * m ** -0.5}


@functools.partial(jax.jit, static_argnums=2)
def _share(p, first, held):
    """One program for every share: ``first`` is traced."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=4, norm_eps=1e-5, norm_topk_prob=True,
        topk_norm_eps=1e-20, scoring="sigmoid", gate_scale=2.448,
        select_bias=p["router_bias"], first_expert=first, residual=False)


def test_the_32_shares_add_up_to_the_uncut_layer_before_its_last_norm():
    """32 chips with ONE of 32 experts each (the file's 32 chips a layer):
    their experts' parts and the shared expert ONCE, summed BEFORE
    ``n_post_mlp`` — a norm is not linear: the normed parts do not add up
    — are the whole layer as the reference has it."""
    p = _expert_layer()
    h = afmoe.rms_norm(p["x"], p["mlp_norm"], 1e-5)
    whole, chosen = afmoe.expert_ffn(h[None], p, k=4, scale=2.448, first=0)
    shared = afmoe.swiglu(h, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    parts = shares_add_up("trinity", p, _share, whole[0], chosen, k=4,
                          shared=shared)
    summed = sum(part for part, _ in parts) + shared
    post = lambda y: afmoe.rms_norm(y, p["mlp_post_norm"], 1e-5)  # noqa: E731
    np.testing.assert_allclose(post(summed), post(whole[0]), atol=2e-5)
    normed_parts = sum(post(part) for part, _ in parts) + post(shared)
    assert float(jnp.max(jnp.abs(normed_parts - post(whole[0])))) > 1.0
    # one share alone is the reference's with the same expert held, less
    # the shared expert the reference adds
    alone, _ = afmoe.expert_ffn(
        h[None], {**p, **{w: p[w][7:8] for w in ("w_gate", "w_up",
                                                  "w_down")}},
        k=4, scale=2.448, first=7)
    np.testing.assert_allclose(parts[7][0] + shared, alone[0], atol=2e-5)
    # the gates sum to route_scale
    gates, _ = afmoe.route(h, p["router"], p["router_bias"], 4, 2.448)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.448, rtol=1e-6)


def test_the_programs_expert_layer_norms_the_sum_once():
    """The layer as the decoder composes it (``blocks/ffn.py``): x +
    n_post_mlp(experts' part + shared expert)."""
    from ray_tpu.models.blocks import FFNS
    from ray_tpu.models.blocks.base import Ctx

    cfg = tiny(embed_dim=32, mlp_dim=16, num_experts=32, experts_held=8,
               first_expert=8, num_selected=4)
    p = _expert_layer()
    lp = {k: v for k, v in p.items() if k != "x"}
    lp.update({w: p[w][8:16] for w in ("w_gate", "w_up", "w_down")})
    x = p["x"][None]
    out, _, counts = FFNS["moe"].apply(
        Ctx(cfg, None, lambda a, _: a, False), x,
        {k: jnp.zeros(()) for k in (
            "aux_loss", *FFNS["moe"].stats(cfg))}, lp)
    h = afmoe.rms_norm(p["x"], p["mlp_norm"], 1e-5)
    want, _ = afmoe.expert_ffn(h[None], lp, k=4, scale=2.448, first=8)
    np.testing.assert_allclose(
        out, x + afmoe.rms_norm(want, p["mlp_post_norm"], 1e-5), atol=2e-5)
    assert counts.shape == (32,)


# -- the statistic and the train step ------------------------------------------

def test_the_window_statistic_is_the_schedules_count():
    """``attn_window_executed_share``: the flash schedule's executed pairs
    over the pairs the window leaves, a ``max`` over the windowed layers —
    1.0624 at the cell's 8192 under 4096 (``causal_tile_counts``)."""
    cfg = tiny(attn_impl="flash")
    counts = lambda cfg: jax.jit(lambda p: loss_and_counts(  # noqa: E731
        p, {"tokens": TOKENS}, cfg))(program("trinity").params)
    _, (metrics, _) = counts(cfg)
    tiles = choose_tiles(SEQ, SEQ, True, 16, jnp.float32, window=WINDOW)
    n = causal_tile_counts(SEQ, SEQ, *tiles, window=WINDOW)
    assert n["causal_pairs"] == 16 * 17 // 2 + 32 * 16
    assert float(metrics["attn_window_executed_share"]) == pytest.approx(
        n["executed_pairs"] / n["causal_pairs"])
    # the XLA form computes the whole square
    _, (metrics, _) = counts(tiny())
    assert float(metrics["attn_window_executed_share"]) == pytest.approx(
        SEQ * SEQ / n["causal_pairs"])
    big = causal_tile_counts(8192, 8192, *choose_tiles(
        8192, 8192, True, 128, jnp.bfloat16, window=4096), window=4096)
    assert big["causal_pairs"] == 25167872
    assert big["executed_pairs"] / big["causal_pairs"] == pytest.approx(
        1.0624, abs=1e-4)


def test_the_train_step_runs_the_windowed_kernels_and_reports():
    stepped = train_step_reports("trinity")
    # ONE backward kernel, windowed or not
    assert "flash_dq" not in stepped.text
    assert float(stepped.metrics["moe_dropped"]) == 0.0
    for run in (1, 2, 3):
        moved = np.asarray(stepped.state.params["layers"][run][
            "router_bias"]) - stepped.before["layers"][run]["router_bias"]
        assert np.all((moved == 0) | np.isclose(
            np.abs(moved), stepped.cfg.bias_update_speed, atol=1e-7))


def test_a_window_is_refused_where_no_kernel_takes_one():
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError):
            attention_block._attention(
                None, None, None, tiny(attn_impl=impl), object(), window=8)
    with pytest.raises(NotImplementedError):
        tiny(layer_types=("mamba",) * 5)
    with pytest.raises(NotImplementedError):
        tiny(block_norm="output")       # the expert layer norms inside


# -- the configuration file ----------------------------------------------------

def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                25024, 256, 8, 0, 1, 5)
    assert (cfg.head_dim, cfg.sliding_window, cfg.attn_output_gate,
            cfg.block_norm, cfg.position_embedding, cfg.qk_head_norm) == (
                128, 4096, True, "sandwich", "rope_windowed", True)
    assert cfg.select_bias and cfg.router_scoring == "sigmoid"
    assert [n for _, n in cfg.kind_runs] == [1, 2, 1, 1]
    assert afmoe.kinds(conf) == cfg.layer_kinds
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              8192)
    assert drawn.shape == (1, 8193) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 24000 < drawn.max() < 25024
