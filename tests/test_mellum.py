"""Mellum2-12B-A2.5B's mechanisms at CPU size in float32: rotary positions
BY THE LAYER'S KIND (YaRN with its attention factor in the full layers, plain
tables in the windowed ones), a window in three attention layers of four,
softmax-scored experts whose gates are renormalised over the chosen, held or
not, with no shared expert, an untied head over a vocabulary slice and a
held share — the program (``ray_tpu/models/llama.py`` and its blocks)
against the benchmark's plain reference (``benchmark/reference/mellum.py``:
nothing shared with the code under test) on seeded weights."""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import mellum
from ray_tpu.models.blocks import attention as attention_block
from ray_tpu.models.llama import ROPE_BY_KIND, forward, loss_and_counts
from ray_tpu.ops.attention import (
    causal_tile_counts, choose_tiles, flash_attention, mha_reference)
from ray_tpu.ops.layers import (
    repeat_kv_heads, rope, scaled_rope, yarn_inv_freq)
from ray_tpu.ops.moe import moe_block
import tiny_models
from tiny_models import (
    F, MELLUM_GROUPS, MELLUM_WINDOW, MELLUM_YARN, ROWS, S,
    against_the_reference, fault_ids, program, seeded, shares_add_up,
    stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-1of4"
CONF, TOKENS = ROWS["mellum"].conf, ROWS["mellum"].tokens
WINDOW, SEQ = MELLUM_WINDOW, TOKENS.shape[1] - 1
YARN, GROUPS = MELLUM_YARN, MELLUM_GROUPS
PLAIN = GROUPS[S]
tiny = functools.partial(tiny_models.tiny, "mellum")
# the published group of the full layers
PUBLISHED = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.2772588722239782}


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "flash-under-the-checkpoint"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """Two periods (s s s f, twice: four runs of layers).  Tolerances: both
    sides are float32, the program at XLA's default matmul precision on the
    CPU and the reference at "highest"; what is left is the order of sums —
    2e-5 relative on means of 96 tokens, 5e-5 nats on one token's loss,
    1e-4 of a gradient's largest entry (the selection is discrete: a swapped
    expert would read 1e-2 and more).  Once with the XLA attention, once
    with the flash kernels (interpreted; the windowed ones in six layers)
    under the layer checkpoint, the chip's path."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    cfg = program("mellum", **kw).cfg
    assert [n for _, n in cfg.kind_runs] == [3, 1, 3, 1]
    assert mellum.kinds(CONF) == cfg.layer_kinds
    total, parts, want, ours = against_the_reference(
        "mellum", parts=("loss", "aux_loss"), nll_atol=5e-5, **kw)
    assert float(total) - float(parts["loss"]) == pytest.approx(
        0.001 * float(want["aux_loss"]), rel=1e-3)
    np.testing.assert_allclose(parts["moe_held_share"],
                               want["moe_held_share"], rtol=1e-6)
    assert 0.1 < float(parts["moe_held_share"]) < 0.5
    assert float(parts["moe_dropped"]) == 0.0
    assert len(want["experts"]) == 8
    for run in ours["layers"]:      # every tensor of every run has one
        assert set(run) == {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                            "router", "w_gate", "w_up", "w_down"}
        assert all(np.any(np.asarray(g)) for g in run.values())


@pytest.mark.parametrize("fault", fault_ids("mellum"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """Each structural point of the configuration, got wrong in ONE PERIOD
    of the program (the row's ``faults`` and ``sound``), moves a token's
    loss by more than a thousandth of a nat (the sound program stands 5e-5
    off at most) — or, of the load-balancing term, the total by more than
    its tolerance: the full layers' tables plain, YaRN's factor left off
    them, the two kinds' tables swapped, YaRN in every layer, the ramp
    between untruncated bounds, the window dropped, a key short or four
    times as wide, the gates not renormalised, the wrong quarter of the
    experts."""
    stands_apart("mellum", fault)


# -- the rotary rule -----------------------------------------------------------

def test_yarn_table_is_the_closed_form_at_the_published_numbers():
    """Dimension 128, theta 5e5, factor 16 over an original range of 8192:
    c(32) = 18.08 and c(1) = 34.98, so pairs 0-18 keep their frequency,
    pairs 35-63 have it divided by 16 and 16 pairs ramp between; the
    tables carry the factor 0.1 ln 16 + 1; positions past 8192 are in the
    sample.  Against numpy in float64."""
    i = np.arange(64, dtype=np.float64)
    plain = 500000.0 ** (-2 * i / 128)
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = plain / 16 * ramp + plain * (1 - ramp)
    got = yarn_inv_freq(128, 500000, factor=16, original=8192)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert np.all(np.asarray(got[:19]) == np.asarray(
        1.0 / 500000 ** (jnp.arange(0, 38, 2, dtype=jnp.float32) / 128)))
    np.testing.assert_allclose(got[35:], want[35:], rtol=2e-6)
    np.testing.assert_allclose(got[35:] * 16, plain[35:], rtol=2e-6)
    assert PUBLISHED["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, abs=1e-15)
    theta = PUBLISHED["rope_theta"]
    scaling = {k: v for k, v in PUBLISHED.items() if k != "rope_theta"}
    cos, sin = scaled_rope(16384, 128, theta, scaling)
    at = np.array([0, 1, 1023, 8191, 8192, 12000, 16383])
    angles = at[:, None] * want[None, :]
    # float32 angles up to 16383 rad carry 1e-3 rad of rounding
    np.testing.assert_allclose(cos[at], 1.2772588722239782 * np.cos(angles),
                               atol=3e-3)
    np.testing.assert_allclose(sin[at], 1.2772588722239782 * np.sin(angles),
                               atol=3e-3)
    # ... and the reference's tables, written from the same equations
    ref_cos, ref_sin = mellum.rope_tables(16384, 128, PUBLISHED)
    np.testing.assert_allclose(cos[at], ref_cos[at], atol=3e-3)
    np.testing.assert_allclose(sin[at], ref_sin[at], atol=3e-3)
    np.testing.assert_allclose(
        mellum.inv_freq(128, PUBLISHED), want, rtol=2e-6)
    # where the group states no factor it is 0.1 ln(factor) + 1; a group of
    # equal mscale and mscale_all_dim leaves the tables alone
    del scaling["attention_factor"]
    np.testing.assert_allclose(scaled_rope(64, 128, theta, scaling)[0],
                               cos[:64], rtol=1e-6)
    latent = dict(scaling, mscale=1, mscale_all_dim=1)
    plain_cos, _ = rope(64, 128, theta, inv_freq=got)
    assert np.all(np.asarray(scaled_rope(64, 128, theta, latent)[0])
                  == np.asarray(plain_cos))
    # a plain group, and none: ``rope`` itself
    for none in ((), None, {"rope_type": "default"}):
        assert np.all(np.asarray(scaled_rope(64, 128, theta, none)[1])
                      == np.asarray(rope(64, 128, theta)[1]))


def test_the_rule_follows_the_kind_of_layer():
    """``rope_rule`` is the ONE place that says which tables a kind of
    layer takes; every layer rotates under ``rope_by_layer_type``."""
    cfg = tiny()
    assert (cfg.rotary(True), cfg.rotary(False)) == (True, True)
    theta, scaling = cfg.rope_rule(False)
    assert (theta, dict(scaling)) == (100, {
        k: v for k, v in YARN.items() if k != "rope_theta"})
    assert cfg.rope_rule(True) == (100, (("rope_type", "default"),))
    hash(cfg)       # the groups are kept hashable, as rope_scaling is
    # a model of ONE rule answers with it for both kinds
    one = tiny(position_embedding="rope", rope_parameters=None,
               rope_theta=5e5, rope_scaling={"type": "yarn", "factor": 4.0,
                                             "original_max_position_embeddings"
                                             : 16})
    assert one.rope_rule(True) == one.rope_rule(False)
    assert one.rope_rule(False)[0] == 5e5
    with pytest.raises(ValueError, match="full_attention"):
        tiny(rope_parameters={S: PLAIN})
    with pytest.raises(ValueError, match="rope_theta"):
        tiny(rope_parameters={F: YARN, S: {"rope_type": "default"}})
    # only the kinds the model has are asked for; a mixer that does not
    # rotate asks for none
    tiny(layer_types=(S,) * 8, rope_parameters={S: PLAIN})
    tiny(layer_types=("linear_attention",) * 8, rope_parameters={},
         num_experts=0, experts_held=0, first_expert=0, gdn_heads=4,
         gdn_key_dim=16, gdn_value_dim=16)
    for field in (dict(rope_parameters={F: dict(YARN, rope_type="llama3"),
                                        S: PLAIN}),
                  dict(position_embedding="rope", rope_parameters=None,
                       rope_scaling={"type": "linear", "factor": 2.0})):
        with pytest.raises(NotImplementedError, match="never trained"):
            tiny(**field)


@pytest.mark.parametrize("mixer", ["softmax", "latent"])
def test_one_rule_a_model_goes_through_the_same_helper(mixer):
    """The small repair: a softmax mixer whose file carries a YaRN
    ``rope_scaling`` is rotated by YaRN's tables (it was trained with plain
    ones, silently), through the helper the latent mixer's tables come
    from — which are what they were, bit for bit."""
    scaling = {"type": "yarn", "factor": 4.0, "mscale": 1, "mscale_all_dim": 1,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1}
    fields = dict(position_embedding="rope", rope_parameters=None,
                  rope_theta=1e4, rope_scaling=scaling, layer_types=(),
                  num_layers=1, num_experts=0, experts_held=0, first_expert=0)
    if mixer == "latent":
        fields.update(q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16,
                      qk_rope_dim=8, v_head_dim=16, num_kv_heads=4)
    cfg = tiny(**fields)
    dim = 8 if mixer == "latent" else 16
    ctx = attention_block.Ctx(cfg, None, lambda a, _: a, False)
    got = attention_block._rope_tables(ctx, False, SEQ, dim)
    want = rope(SEQ, dim, 1e4, inv_freq=yarn_inv_freq(
        dim, 1e4, factor=4.0, original=16))
    for ours, theirs, plain in zip(got, want, rope(SEQ, dim, 1e4)):
        assert np.all(np.asarray(ours) == np.asarray(theirs))
        assert float(jnp.max(jnp.abs(ours - plain))) > 0.1
    if mixer == "latent":   # the softmax scale keeps mscale_all_dim's square
        assert attention_block._sm_scale(cfg) == 24 ** -0.5 * (
            0.1 * math.log(4.0) + 1.0) ** 2
    else:
        assert attention_block._sm_scale(cfg) == 16 ** -0.5
        logits, plain = (
            jax.jit(lambda p: forward(p, TOKENS[:, :-1], c)[0])(seeded(cfg))
            for c in (cfg, dataclasses.replace(cfg, rope_scaling=None)))
        assert float(jnp.max(jnp.abs(logits - plain))) > 1e-3


def test_the_view_rope_works_on_follows_the_norm_and_the_mesh_not_the_sums(
        monkeypatch):
    """Where a head fills whole lane blocks the softmax mixers rotate ``(b,
    s, heads x d)`` by the rotation's kernel (``ops/rotary.py``) — one row
    or two alike, so a cell's one-row check runs what its step runs —
    unless a per-head norm holds q and k to four dimensions, 'tp' shards
    the lanes or the region is manual over 'sp'; then, and at a narrower
    head, ``apply_rope`` on the 4-D view.  Both kinds of layer, the logits
    of the two routes agree to float32's last places."""
    from ray_tpu.parallel import MeshConfig, make_mesh, use_mesh

    cfg = tiny(head_dim=128)

    def flat(cfg, mesh=None, sp_manual=False):
        return attention_block._rotates_flat(
            attention_block.Ctx(cfg, mesh, lambda a, _: a, sp_manual), SEQ)

    devices = jax.devices()[:4]
    assert flat(cfg)
    assert not flat(tiny())                     # a head of 16 lanes
    assert not flat(dataclasses.replace(cfg, qk_head_norm=True))
    assert flat(cfg, make_mesh(MeshConfig(fsdp=2), devices=devices[:2]))
    assert not flat(cfg, make_mesh(MeshConfig(fsdp=2, tp=2), devices=devices))
    assert not flat(cfg, sp_manual=True)
    params, inputs = seeded(cfg), (TOKENS[:, :-1], TOKENS[:1, :-1])

    def routes(cfg, params=params):
        """(the kernel, ``apply_rope``'s halves of a head) in the program
        of two rows and of one."""
        found = []
        for t in inputs:
            text = str(jax.make_jaxpr(
                lambda p, t: forward(p, t, cfg)[0])(params, t))
            half_a_head = (f"f32[{t.shape[0]},{SEQ},{cfg.num_heads},"
                           f"{cfg.head_dim // 2}]")
            found.append(("rope_fwd" in text, half_a_head in text))
        return found

    assert routes(cfg) == [(True, False)] * 2
    by_norm = dataclasses.replace(cfg, qk_head_norm=True)
    assert routes(by_norm, seeded(by_norm)) == [(False, True)] * 2
    assert routes(tiny(), seeded(tiny())) == [(False, True)] * 2
    # a program a call: what is traced follows the patch below
    logits = lambda t: jax.jit(  # noqa: E731
        lambda p: forward(p, t, cfg)[0])(params)
    by_rows = [logits(t) for t in inputs]
    # under a mesh that leaves the lanes whole: per shard of the batch,
    # and into the flash kernels with their pre-scale on q already
    mesh = make_mesh(MeshConfig(fsdp=2), devices=devices[:2])
    flash = dataclasses.replace(cfg, attn_impl="flash")
    with use_mesh(mesh):
        sharded = jax.jit(
            lambda p: forward(p, inputs[0], flash, mesh=mesh)[0])(params)
    assert float(jnp.max(jnp.abs(sharded - by_rows[0]))) < 1e-4 * float(
        jnp.max(jnp.abs(by_rows[0])))
    monkeypatch.setattr(attention_block, "_rotates_flat",
                        lambda ctx, s: False)
    assert routes(cfg) == [(False, True)] * 2
    for got, tokens in zip(by_rows, inputs):
        want = logits(tokens)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
            jnp.max(jnp.abs(want)))


# -- the window no wider than half a tile --------------------------------------

@pytest.mark.parametrize("window", [200, 50, 128],
                         ids=["under-half-a-tile", "under-a-sub-tile",
                              "one-sub-tile"])
def test_a_narrow_window_through_the_flash_kernels_at_a_group_of_8(window):
    """Mellum2's shape in small: 8 query heads a KV head, a head of 128
    lanes (read in place), a window of at most half the fetch tile (512
    here, 2048 on the chip) — both edges inside most tiles it touches — and
    one below the compute sub-tile.  Forward and backward against the XLA
    reference, in interpret mode."""
    b, s, h, h_kv, d = 1, 512, 8, 1, 128
    keys = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, h_kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, h_kv, d), jnp.float32)
    do = jax.random.normal(keys[3], (b, s, h, d), jnp.float32)
    tiles = choose_tiles(s, s, True, d, jnp.float32, window=window)
    assert tiles[0] == 512 and window <= tiles[0] // 2

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=True)

    def theirs(q, k, v):
        return mha_reference(q, *repeat_kv_heads(q, k, v), causal=True,
                             window=window)

    def value_and_pull(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return out, pull(do)

    out, grads = jax.jit(lambda: value_and_pull(ours))()
    want, want_grads = jax.jit(lambda: value_and_pull(theirs))()
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=1e-4)


def test_the_window_statistics_are_the_schedules_counts():
    """``attn_window_executed_share`` and ``attn_window_masked_tile_share``,
    a ``max`` over the windowed layers: at the cell's 16384 under 1024 the
    schedule keeps sub-tiles of 256, executes 1.25 times the pairs the
    window leaves — 12.1 % of the causal ones — and masks 40 % of the 310
    sub-tiles it runs (Trinity's 8192 under 4096: 1.0624)."""
    counts = lambda cfg: jax.jit(lambda p: loss_and_counts(  # noqa: E731
        p, {"tokens": TOKENS}, cfg))(seeded(cfg))
    _, (metrics, _) = counts(tiny(attn_impl="flash", num_layers=4))
    tiles = choose_tiles(SEQ, SEQ, True, 16, jnp.float32, window=WINDOW)
    n = causal_tile_counts(SEQ, SEQ, *tiles, window=WINDOW)
    assert float(metrics["attn_window_executed_share"]) == pytest.approx(
        n["executed_pairs"] / n["causal_pairs"])
    assert float(metrics["attn_window_masked_tile_share"]) == pytest.approx(
        n["diagonal"] / (n["diagonal"] + n["interior"]))
    # the XLA form computes, and masks, the whole square
    _, (metrics, _) = counts(tiny(num_layers=4))
    assert float(metrics["attn_window_masked_tile_share"]) == 1.0
    tiles = choose_tiles(16384, 16384, True, 128, jnp.bfloat16, window=1024)
    assert tiles == (2048, 2048, 256, 256)
    big = causal_tile_counts(16384, 16384, *tiles, window=1024)
    assert (big["interior"], big["diagonal"], big["dead"]) == (186, 124, 3786)
    assert big["causal_pairs"] == 1024 * 1025 // 2 + 15360 * 1024 == 16253440
    assert big["causal_pairs"] / (16384 * 16385 // 2) == pytest.approx(
        0.1211, abs=1e-4)
    assert big["executed_pairs"] / big["causal_pairs"] == pytest.approx(
        1.25, abs=1e-3)
    assert big["diagonal"] / 310 == pytest.approx(0.4)


# -- the shares ----------------------------------------------------------------

def _expert_layer(seed=3, tokens=96, d=32, m=16, experts=64):
    rng = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa
    return {"x": n(tokens, d), "mlp_norm": 1.0 + 0.1 * n(d),
            "router": n(d, experts) * d ** -0.5,
            "w_gate": n(experts, d, m) * d ** -0.5,
            "w_up": n(experts, d, m) * d ** -0.5,
            "w_down": n(experts, m, d) * m ** -0.5}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _share(p, first, held, renormalise=True):
    """One program for every share: ``first`` is traced."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=8, norm_eps=1e-6, norm_topk_prob=renormalise,
        scoring="softmax", first_expert=first, residual=False)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """4 chips with 16 of 64 experts each (the file's 4 chips a layer), no
    shared expert: their parts, summed, are the whole layer as the
    reference has it — the gates renormalised over the 8 CHOSEN, wherever
    they live, so a share's gates do not sum to 1."""
    p = _expert_layer()
    h = mellum.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    whole, chosen, balance = mellum.expert_ffn(
        h[None], p, k=8, renormalise=True, first=0)
    parts = shares_add_up("mellum", p, _share, whole[0], chosen, k=8)
    for _, s in parts:  # ... and reports the same loss over all 64
        assert 0.1 < float(s["held_share"]) < 0.4
        np.testing.assert_allclose(s["aux_loss"], balance, rtol=1e-5)
    # one share alone is the reference's with the same experts held
    alone, _, _ = mellum.expert_ffn(
        h[None], {**p, **{w: p[w][32:48] for w in ("w_gate", "w_up",
                                                   "w_down")}},
        k=8, renormalise=True, first=32)
    np.testing.assert_allclose(parts[2][0], alone[0], atol=2e-5)
    # renormalised over the chosen: the 8 gates sum to 1, the held ones less
    _, gates, experts, _ = mellum.route(h, p["router"], 8, True)
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, rtol=1e-6)
    held = jnp.sum(jnp.where(experts < 16, gates, 0.0), -1)
    assert 0.05 < float(jnp.mean(held)) < 0.6
    # gates left as the softmax gives them are another layer
    raw, _ = _share(p, 0, 16, renormalise=False)
    assert float(jnp.max(jnp.abs(raw - parts[0][0]))) > 0.05


# -- the train step and the configuration file ---------------------------------

def test_the_train_step_runs_both_kinds_of_kernel_and_reports():
    stepped = train_step_reports("mellum")
    text = stepped.text
    assert "flash_dq" not in text   # ONE backward kernel, windowed or not
    # the rotary ops sit INSIDE attn_qkv: no name stack starts at ``rope``
    assert "/rope/" in text and "jit(step)/rope" not in text
    assert float(stepped.metrics["moe_dropped"]) == 0.0


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                24576, 64, 16, 0, 0, 4)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.sliding_window, cfg.num_selected,
            cfg.norm_topk_prob, cfg.router_scoring, cfg.shared_experts,
            cfg.select_bias, cfg.tie_embeddings, cfg.norm_eps) == (
                2304, 32, 4, 128, 896, 1024, 8, True, "softmax", 0, False,
                False, 1e-6)
    assert (cfg.position_embedding, cfg.qk_norm, cfg.qk_head_norm,
            cfg.num_nextn, cfg.aux_loss_coef, cfg.z_loss_coef) == (
                ROPE_BY_KIND, False, False, 0, 0.001, 0.0)
    assert cfg.kind_runs == (((S, "moe"), 3), ((F, "moe"), 1))
    assert mellum.kinds(conf) == cfg.layer_kinds
    theta, scaling = cfg.rope_rule(False)
    assert dict(scaling, rope_theta=theta) == PUBLISHED == \
        conf["rope_parameters"][F]
    assert cfg.rope_rule(True) == (500000, (("rope_type", "default"),))
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              16384)
    assert drawn.shape == (1, 16385) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 24000 < drawn.max() < 24576
