"""Laguna-XS.2's mechanisms at CPU size in float32: a count of query heads
that follows the layer's KIND (4 in a full layer, 6 under the window, over 2
KV heads), rotary positions over a SHARE of a head in the full layers (YaRN
reckoned over the rotary width, its factor on the rotated half alone), a gate
that is one number a head, each layer's FFN by a per-layer list, sigmoid-scored
experts with a selection bias beside a shared expert, an untied head over a
vocabulary slice and a held share — the program (``ray_tpu/models/llama.py``
and its blocks) against the benchmark's plain reference
(``benchmark/reference/laguna.py``: nothing shared with the code under test)
on seeded weights."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import afmoe, laguna
from ray_tpu.models.blocks import attention as attention_block
from ray_tpu.models.llama import ROPE_BY_KIND, forward, init_params
from ray_tpu.ops.layers import apply_rope, scaled_rope, yarn_inv_freq
import tiny_models
from tiny_models import (
    F, LAGUNA_GROUPS, LAGUNA_YARN, ROWS, S,
    against_the_reference, expert_layer, fault_ids, program, seeded, share,
    shares_add_up, stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "laguna-xs.2-33b-a3b-1of8"
CONF, TOKENS = ROWS["laguna"].conf, ROWS["laguna"].tokens
SEQ = TOKENS.shape[1] - 1
tiny = functools.partial(tiny_models.tiny, "laguna")
# the published group of the full layers
PUBLISHED = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5}


# -- the model against the reference ------------------------------------------

def test_a_runs_tensors_have_its_kinds_head_count():
    """F+dense | S S S | F | S S S: four stacks; ``wq``, ``wo`` and the
    gate of a sliding run are 6 heads wide, a full run's 4; k and v are the
    model's 2 KV heads in both; the gate is ONE column a head."""
    cfg = tiny()
    assert cfg.kind_runs == (((F, "dense"), 1), ((S, "moe"), 3),
                             ((F, "moe"), 1), ((S, "moe"), 3))
    assert laguna.kinds(CONF) == cfg.layer_kinds
    assert (cfg.q_heads(False), cfg.q_heads(True), cfg.num_heads) == (4, 6, 4)
    assert dict(laguna.heads_by_kind(CONF)) == {F: 4, S: 6}
    layers = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    mixer = ["attn_norm", "wq", "wk", "wv", "wo", "wg"]
    assert list(layers[0]) == mixer + ["mlp_norm", "w_gate", "w_up", "w_down"]
    for run, (n, heads) in enumerate(((1, 4), (3, 6), (1, 4), (3, 6))):
        assert layers[run]["wq"].shape == (n, 64, heads * 16)
        assert layers[run]["wo"].shape == (n, heads * 16, 64)
        assert layers[run]["wg"].shape == (n, 64, heads)
        assert layers[run]["wk"].shape == layers[run]["wv"].shape == (
            n, 64, 32)
        if run:
            assert list(layers[run]) == mixer + [
                "mlp_norm", "router", "w_gate", "w_up", "w_down",
                "router_bias", "shared_gate", "shared_up", "shared_down"]
            assert layers[run]["w_gate"].shape == (n, 4, 64, 32)
    # a gate a head AND channel (Trinity's form) is as wide as wq
    wide = init_params(jax.random.PRNGKey(0), tiny(attn_output_gate=True))
    assert [run["wg"].shape[-1] for run in wide["layers"]] == [64, 96, 64, 96]
    # a model without the list: ``num_heads`` in every layer, and no counter
    plain = tiny(heads_per_layer=())
    assert (plain.q_heads(False), plain.q_heads(True)) == (4, 4)
    assert attention_block.SOFTMAX.stats(plain) == {}
    assert set(attention_block.SLIDING.stats(plain)) == set(
        attention_block.WINDOW_STATS)
    assert set(attention_block.SLIDING.stats(cfg)) - set(
        attention_block.WINDOW_STATS) == {"attn_q_heads_window",
                                          "attn_window_keys"}
    assert set(attention_block.SOFTMAX.stats(cfg)) == {
        "attn_q_heads_full", "attn_rotary_width_full"}


@pytest.mark.parametrize("impl", ["reference", "flash-under-the-checkpoint"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """Two periods (f s s s, twice: four runs of layers).  Tolerances as
    Mellum's row: both sides float32, the program at XLA's default matmul
    precision on the CPU and the reference at "highest".  Once with the
    XLA attention, once with the flash kernels (interpreted; groups of 2
    and 3 query heads a KV head) under the layer checkpoint, the chip's
    path.  Every tensor of every run has a gradient — the gate's, both
    kinds' ``wq`` — and the counters read the tiny model's own numbers."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    total, parts, want, ours = against_the_reference(
        "laguna", nll_atol=5e-5, **kw)
    assert float(total) == pytest.approx(float(parts["loss"]), rel=1e-6)
    np.testing.assert_allclose(parts["moe_held_share"],
                               want["moe_held_share"], rtol=1e-6)
    assert 0.1 < float(parts["moe_held_share"]) < 0.5
    assert float(parts["moe_dropped"]) == 0.0
    assert len(want["experts"]) == 7
    assert (float(parts["attn_q_heads_full"]),
            float(parts["attn_q_heads_window"]),
            float(parts["attn_rotary_width_full"]),
            float(parts["attn_window_keys"])) == (4.0, 6.0, 8.0, 16.0)
    for run in ours["layers"]:      # the selection bias takes none
        assert all(np.any(np.asarray(g)) == (name != "router_bias")
                   for name, g in run.items())


@pytest.mark.parametrize("fault", fault_ids("laguna"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """Each structural point of the configuration, got wrong in ONE PERIOD
    of the program (the row's ``faults`` and ``sound``), moves a token's
    loss by more than a thousandth of a nat (the sound program stands 5e-5
    off at most): the full layers' head count in the sliding layers, the
    whole head rotated in the full layer, YaRN's factor on the half that
    passes through, YaRN reckoned over the head, the factor or YaRN left
    off, the gate left out, the window a key short or dropped, the
    router's scores, bias, scale, renormalisation, the shared expert, the
    wrong eighth of the experts."""
    stands_apart("laguna", fault)


# -- the rotation --------------------------------------------------------------

def test_half_a_head_is_rotated_and_the_other_half_passes_bit_for_bit():
    """``_rotated`` on a full layer's q and k: the first 8 of a head's 16
    dimensions are ``apply_rope``'s under the tables reckoned over 8 — the
    reference's ``rotate`` —, dimensions 8..15 leave as they came, to the
    bit, and carry no factor; a sliding layer's whole head turns."""
    cfg = tiny()
    ctx = attention_block.Ctx(cfg, None, lambda a, _: a, False)
    assert (cfg.rotary_dim(False), cfg.rotary_dim(True)) == (8, 16)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(keys[0], (2, SEQ, 4, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, SEQ, 2, 16), jnp.float32)
    got_q, got_k = jax.jit(
        lambda q, k: attention_block._rotated(ctx, False, q, k))(q, k)
    for got, x in ((got_q, q), (got_k, k)):
        assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
        np.testing.assert_allclose(got, laguna.rotate(x, LAGUNA_YARN),
                                   atol=2e-6)
        assert float(jnp.max(jnp.abs(got[..., :8] - x[..., :8]))) > 0.5
    # the tables: YaRN over dim = 8, cos and sin times the factor
    cos, sin = attention_block._rope_tables(ctx, False, SEQ, 8)
    theta, scaling = cfg.rope_rule(False)
    want = scaled_rope(SEQ, 8, theta, scaling)
    assert np.array_equal(np.asarray(cos), np.asarray(want[0]))
    assert float(cos[0, 0]) == pytest.approx(LAGUNA_YARN["attention_factor"])
    np.testing.assert_allclose(
        apply_rope(q[..., :8], cos, sin), got_q[..., :8], atol=1e-6)
    # reckoned over the head's 16 the frequencies are others
    over_head = yarn_inv_freq(16, 100, factor=4, original=16, beta_fast=64)
    over_width = yarn_inv_freq(8, 100, factor=4, original=16, beta_fast=64)
    assert float(jnp.max(jnp.abs(over_head[:4] / over_width - 1))) > 0.3
    # a sliding layer: the whole head, plain tables, no factor
    whole_q, _ = attention_block._rotated(ctx, True, q, k)
    plain = scaled_rope(SEQ, 16, 100, ())
    np.testing.assert_allclose(whole_q, apply_rope(q, *plain), atol=1e-6)
    assert float(jnp.max(jnp.abs(whole_q[..., 8:] - q[..., 8:]))) > 0.5


def test_the_published_rule_over_sixty_four_dimensions():
    """At the published numbers the full layers rotate 64 of 128 dimensions
    by YaRN x64 from 4096 at theta 5e5 with ``beta_fast`` 64: c(64) = 5.66
    and c(1) = 15.81, so pairs 0-5 keep their frequency, pairs 16-31 have
    it divided by 64 and ten ramp between; the factor is 0.1 ln 64 + 1.
    Program and reference from the same equations, against numpy."""
    i = np.arange(32, dtype=np.float64)
    plain = 500000.0 ** (-2 * i / 64)
    c = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(500000))
    assert (math.floor(c(64)), math.ceil(c(1))) == (5, 16)
    ramp = np.clip((i - 5) / 11, 0, 1)
    want = plain / 64 * ramp + plain * (1 - ramp)
    got = yarn_inv_freq(64, 500000, factor=64, original=4096, beta_fast=64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(laguna.rope_tables(8, 64, PUBLISHED)[0][1],
                               1.4158883083359672 * np.cos(want), rtol=1e-5)
    assert PUBLISHED["attention_factor"] == pytest.approx(
        0.1 * math.log(64) + 1, abs=1e-15)
    assert laguna.rotary_width(128, PUBLISHED) == 64
    # the flat kernel swaps the halves of a WHOLE head: a kind that rotates
    # a share of one goes by the 4-D view, the other by the kernel
    cfg = tiny(head_dim=128)
    params, tokens = seeded(cfg), TOKENS[:1, :-1]
    text = str(jax.make_jaxpr(lambda p: forward(p, tokens, cfg)[0])(params))
    assert text.count("rope_fwd") == 2 * 2      # q and k of two sliding runs
    assert f"f32[1,{SEQ},4,32]" in text         # half of the rotated half


def test_the_two_refusals_and_what_else_the_lists_ask():
    """A file that gives one kind of layer two head counts, and a rotary
    share that leaves no whole pair of dimensions, are REFUSED; so are a
    count that is not whole groups of the KV heads, a list shorter than
    the model, a mixer that does not read the list, and a gate of no known
    form."""
    with pytest.raises(ValueError, match="ONE count a kind"):
        tiny(heads_per_layer=(4, 6, 6, 4) * 2)
    with pytest.raises(ValueError, match="ONE count a kind"):
        tiny(heads_per_layer=(4, 6, 6, 6, 6, 6, 6, 6))
    odd = dict(LAGUNA_GROUPS, **{F: dict(LAGUNA_YARN,
                                         partial_rotary_factor=0.3125)})
    with pytest.raises(ValueError, match="no multiple of 2"):     # 5 of 16
        tiny(rope_parameters=odd)
    with pytest.raises(ValueError, match="no multiple of 2"):
        tiny(rope_parameters=dict(LAGUNA_GROUPS, **{
            S: dict(LAGUNA_GROUPS[S], partial_rotary_factor=1.5)}))
    with pytest.raises(ValueError, match="whole groups"):
        tiny(heads_per_layer=(4, 5, 5, 5) * 2)
    with pytest.raises(ValueError, match="names 4 layers"):
        tiny(heads_per_layer=(4, 6, 6, 6))
    with pytest.raises(NotImplementedError, match="softmax mixers"):
        tiny(layer_types=(F, "mamba", S, S) * 2, ssm_heads=8)
    with pytest.raises(ValueError, match="attn_output_gate"):
        tiny(attn_output_gate="per_channel")
    with pytest.raises(ValueError, match="mlp_layer_types"):
        tiny(mlp_layer_types=("dense", "moe") * 4)
    with pytest.raises(ValueError, match="mlp_layer_types"):
        tiny(leading_dense=1)
    # the lists beyond the depth are not the model: a cut keeps them whole
    assert tiny(num_layers=4).kind_runs == (((F, "dense"), 1),
                                            ((S, "moe"), 3))
    # a dense layer anywhere: the list says, not a leading count
    late = tiny(mlp_layer_types=("sparse",) * 7 + ("dense",))
    assert late.layer_kinds[-1] == (S, "dense")
    hash(tiny())    # the lists and the groups are kept hashable


def test_one_number_a_head_gates_that_heads_output():
    """``_out`` with a ``(b, s, heads)`` gate: head i's 16 columns times
    ``sigmoid(g_i)``, then ``wo`` and the add."""
    cfg = tiny()
    ctx = attention_block.Ctx(cfg, None, lambda a, _: a, False)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(keys[0], (2, SEQ, 64), jnp.float32)
    o = jax.random.normal(keys[1], (2, SEQ, 6, 16), jnp.float32)
    gate = jax.random.normal(keys[2], (2, SEQ, 6), jnp.float32)
    wo = jax.random.normal(keys[3], (96, 64), jnp.float32) / 8
    got = attention_block._out(ctx, x, o, {"wo": wo}, True, gate)
    want = x + (o * jax.nn.sigmoid(gate)[..., None]).reshape(2, SEQ, 96) @ wo
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(got - (x + o.reshape(2, SEQ, 96) @ wo)))) > 1


# -- the shares ----------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """8 chips with 4 of 32 experts each (the file's 8 chips a layer):
    their experts' parts and the shared expert ONCE are the whole layer as
    the reference has it; every share routes over all 32 and counts the
    same 4 assignments a token; the gates sum to the scale."""
    p = expert_layer()
    h = afmoe.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    whole, chosen = afmoe.expert_ffn(h[None], p, k=4, scale=2.5, first=0)
    shared = afmoe.swiglu(h, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    parts = shares_add_up(
        "laguna", p, lambda p, first, held: share(p, first, held, 4, 2.5),
        whole[0], chosen, k=4, shared=shared)
    for _, s in parts:
        assert 0.0 < float(s["held_share"]) < 0.3
    gates, _ = afmoe.route(h, p["router"], p["router_bias"], 4, 2.5)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.5, rtol=1e-6)


# -- the train step and the configuration file ---------------------------------

def test_the_train_step_runs_both_kinds_of_kernel_and_reports():
    stepped = train_step_reports("laguna")
    text = stepped.text
    assert "flash_dq" not in text   # ONE backward kernel, windowed or not
    # the new scopes sit INSIDE the step's: no name stack starts at them
    for scope in ("rope_partial", "attn_head_gate"):
        assert f"jit(step)/{scope}" not in text
    m = stepped.metrics
    assert (float(m["attn_q_heads_full"]), float(m["attn_q_heads_window"]),
            float(m["attn_rotary_width_full"]),
            float(m["attn_window_keys"])) == (4.0, 6.0, 8.0, 16.0)
    assert float(m["moe_dropped"]) == 0.0
    moved = np.asarray(stepped.state.params["layers"][1]["router_bias"]) \
        - stepped.before["layers"][1]["router_bias"]
    assert np.all((moved == 0) | np.isclose(
        np.abs(moved), stepped.cfg.bias_update_speed, atol=1e-7))


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                12544, 256, 32, 0, 0, 8)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.dense_width, cfg.shared_width,
            cfg.sliding_window, cfg.num_selected, cfg.norm_topk_prob,
            cfg.router_scoring, cfg.select_bias, cfg.routed_scaling_factor,
            cfg.tie_embeddings, cfg.norm_eps, cfg.attn_output_gate) == (
                2048, 48, 8, 128, 512, 8192, 512, 512, 8, True, "sigmoid",
                True, 2.5, False, 1e-6, "per_head")
    assert (cfg.q_heads(False), cfg.q_heads(True)) == (48, 64)
    assert (cfg.rotary_dim(False), cfg.rotary_dim(True)) == (64, 128)
    assert (cfg.position_embedding, cfg.qk_norm, cfg.qk_head_norm,
            cfg.num_nextn, cfg.aux_loss_coef, cfg.block_norm) == (
                ROPE_BY_KIND, False, False, 0, 0.0, "input")
    assert cfg.kind_runs == (((F, "dense"), 1), ((S, "moe"), 3),
                             ((F, "moe"), 1), ((S, "moe"), 3))
    assert laguna.kinds(conf) == cfg.layer_kinds
    assert dict(laguna.heads_by_kind(conf)) == {F: 48, S: 64}
    theta, scaling = cfg.rope_rule(False)
    assert dict(scaling, rope_theta=theta) == PUBLISHED == \
        conf["rope_parameters"][F]
    assert cfg.rope_rule(True) == (10000, (("partial_rotary_factor", 1),
                                           ("rope_type", "default")))
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))["layers"]
    assert [(s["wq"].shape, s["wg"].shape) for s in shapes] == [
        ((n, 2048, h * 128), (n, 2048, h))
        for n, h in ((1, 48), (3, 64), (1, 48), (3, 64))]
    assert (laguna.STEP_METRICS["attn_q_heads_full"],
            laguna.STEP_METRICS["attn_q_heads_window"],
            laguna.STEP_METRICS["attn_rotary_width_full"],
            laguna.STEP_METRICS["attn_window_keys"]) == (
                ("max", 48.0), ("max", 64.0), ("max", 64.0), ("max", 512.0))
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              16384)
    assert drawn.shape == (1, 16385) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 12000 < drawn.max() < 12544


def test_the_other_models_programs_know_nothing_of_the_lists():
    """A model without ``heads_per_layer`` traces what it traced: no
    counter, no ``rope_partial``, no ``attn_head_gate`` in its program
    (Mellum's row, which shares every function this model changed)."""
    side = program("mellum", num_layers=4)
    text = jax.jit(
        lambda p: forward(p, ROWS["mellum"].tokens[:, :-1], side.cfg)
    ).lower(side.params).as_text(debug_info=True)
    assert "rope_partial" not in text and "attn_head_gate" not in text
    assert "/rope/" in text
    _, parts = side.loss(side.params)
    assert not {"attn_q_heads_full", "attn_q_heads_window",
                "attn_rotary_width_full", "attn_window_keys"} & set(parts)
    assert side.cfg.q_heads(True) == side.cfg.num_heads
