"""Solar-Open-2's block at CPU size — KDA layers whose write strength is
``2 sigmoid`` (``kda_neg_eigval``: eigenvalues of the transition in (-1, 1))
at an inner width of twice the hidden size, three to one GATED softmax
layer of grouped KV heads WITHOUT any position signal, the softmax layers
named by a 0-based list (``gqa_layers``), every layer an expert layer with
sigmoid scores, a selection bias, a shared expert and a held share — the
program (``ray_tpu/models/llama.py`` and its blocks) against the plain
reference (``benchmark/reference/solar_open2.py``: the recurrence a token
at a time, nothing shared with the code under test) on seeded weights, in
float32.  The rule at ``beta`` in (1, 2) and its kernels:
``tests/test_kda.py``."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import solar_open2, xing4
from ray_tpu.models.blocks import kda
from ray_tpu.models.blocks.kda import (
    KDA_BETA_MAX, KDA_CHUNK_DECAY_MIN, KDA_STATE_ABSMAX)
from ray_tpu.models.llama import LlamaConfig, loss_fn
from ray_tpu.ops.delta import kda_kernels_fit
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
import tiny_models
from tiny_models import (
    SOLAR_LINEAR, against_the_reference, expert_layer, fault_ids, program,
    share, shares_add_up, stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "solar-open2-250b-1of32"
HIGHEST = jax.default_matmul_precision("highest")
TOKENS = tiny_models.ROWS["solar"].tokens
tiny = functools.partial(tiny_models.tiny, "solar")


# -- (a) the whole model against the reference ---------------------------------

# the KDA sizes at ONE head of the published 128 / 128: the Pallas pair runs
PUBLISHED_HEAD = tiny_models.Frozen(SOLAR_LINEAR, num_heads=1, head_dim=128)


@pytest.mark.parametrize("impl", ["reference", "flash", "kda-kernels"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """G K K K through ``loss_fn`` — with the XLA attention, and with the
    flash kernels (4 query heads on 2 KV heads, interpreted) under the
    layer checkpoint — against the benchmark's reference, which computes
    the recurrence a token at a time with ``beta = 2 sigmoid``: the loss
    within 2e-6 (relative), each position's loss within 3e-5 nats, every
    gradient leaf within 5e-4 of its scale (float32 against float32 in
    another order of sums: the chunk's inverse, whose entries the factor 2
    doubles, and the levels' products).  The write strength passed 1 and
    stayed under 2.  ``kda-kernels``: the same with the KDA layers at one
    head of 128 / 128, so that ``kdarule_fwd`` / ``kdarule_bwd`` run
    (interpreted) UNDER THE LAYER CHECKPOINT, which since PR 65 keeps the
    kernel's output, the pairs' inverses and a state a grid step by name:
    the backward kernel gets them from the checkpoint's stack and the
    gradients are still the reference's."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    conf = None
    if impl == "kda-kernels":
        kw["linear_attn_config"] = PUBLISHED_HEAD
        conf = {"linear_attn_config": PUBLISHED_HEAD}
        cfg = program("solar", **kw).cfg
        assert kda_kernels_fit(cfg.kda_head_dim, cfg.kda_head_dim, 64)
    assert program("solar", **kw).cfg.kind_runs == (
        (("attention", "moe"), 1), (("kda", "moe"), 3))
    _, parts, _, ours = against_the_reference(
        "solar", parts=("loss", "moe_held_share"), rtol=2e-6, nll_atol=3e-5,
        grad_rtol=5e-4, conf=conf, **kw)
    assert float(parts["moe_dropped"]) == 0.0
    assert 1.0 < float(parts[KDA_BETA_MAX]) < 2.0
    assert 0.05 < float(parts[KDA_STATE_ABSMAX]) < 100.0
    assert float(parts[KDA_CHUNK_DECAY_MIN]) < 0.0
    assert set(solar_open2.STEP_METRICS) <= set(parts)
    # the gate is a tensor of the softmax run and takes a gradient
    assert float(jnp.max(jnp.abs(ours["layers"][0]["wg"]))) > 0.0
    # no gradient reaches a selection bias
    assert not np.any(np.asarray(ours["layers"][1]["router_bias"]))


@pytest.mark.parametrize("fault", fault_ids("solar"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """What each part is worth to the loss (the row's ``faults``): ``beta``
    without its 2, the output gate left out, a rotation of q and k, each
    part of the gates, the other chip's experts, every query head its own
    KV head, the softmax layer in another place.  THE ROTATION of one
    layer in four moves the MEAN of 192 positions by less than its
    tolerance (signed differences cancel): it is the PER-TOKEN comparison
    that sees it."""
    stands_apart("solar", fault)


# -- (b) the public keys: which layer is what, the field, the statistic --------

def test_the_list_names_the_softmax_layers_from_zero():
    cfg = tiny()
    assert [m for m, _ in cfg.layer_kinds] == ["attention"] + ["kda"] * 3
    assert [f for _, f in cfg.layer_kinds] == ["moe"] * 4
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_rank,
            cfg.kda_conv, cfg.embed_dim) == (4, 16, 64, 16, 4, 32)
    assert solar_open2.kinds(tiny_models.ROWS["solar"].conf) \
        == cfg.layer_kinds
    assert hash(cfg) == hash(tiny())        # list and group are frozen
    assert cfg.rotary(False) is False and cfg.attn_output_gate
    # entries past the depth name layers that are not run
    nine = tiny(num_layers=9)
    assert nine.layer_runs == (("attention", 1), ("kda", 3)) * 2 + (
        ("attention", 1),)
    assert tiny(gqa_layers=(1, 2)).layer_runs == (
        ("kda", 1), ("attention", 2), ("kda", 1))
    for clash in (dict(layer_types=("kda",) * 4), dict(kv_lora_rank=16),
                  dict(layer_pattern="M*M*"),
                  dict(linear_attn_config=dict(SOLAR_LINEAR, kda_layers=[1])),
                  dict(linear_attn_config=dict(SOLAR_LINEAR, num_kv_heads=2))):
        with pytest.raises(ValueError, match="gqa_layers names the softmax"):
            tiny(**clash)
    with pytest.raises(ValueError, match="takes its heads"):
        tiny(linear_attn_config=None)
    # value heads given as many as the key heads are the same model
    assert tiny(linear_attn_config=dict(SOLAR_LINEAR, num_kv_heads=4)
                ).layer_kinds == cfg.layer_kinds
    # a model without the list reads its lists or its layer_types as before
    kimi = tiny_models.tiny("kimi")
    assert kimi.gqa_layers == () and not kimi.kda_neg_eigval
    assert [m for m, _ in kimi.layer_kinds] == ["kda"] * 3 + ["latent", "kda"]


def test_the_write_strength_is_doubled_under_the_field_and_nowhere_else():
    """``kda_neg_eigval``: the block multiplies ``sigmoid`` by 2 and reports
    ``kda_beta_max``; without the field the statistic does not exist (a
    model that lacks the key builds what it built: Kimi-Linear's step has
    the metrics it had) and the largest ``sigmoid`` is under 1."""
    on, off = tiny(), tiny(kda_neg_eigval=False)
    assert kda.BLOCK.stats(on) == {**kda.STATS, KDA_BETA_MAX: "max"}
    assert kda.BLOCK.stats(off) == kda.STATS == kda.BLOCK.stats(
        tiny_models.tiny("kimi"))
    params = program("solar").params
    doubled = program("solar").loss(params)[1]
    plain = program("solar", kda_neg_eigval=False).loss(params)[1]
    assert KDA_BETA_MAX not in plain
    assert KDA_BETA_MAX not in program("kimi").loss(
        program("kimi").params)[1]
    # the largest sigmoid of the draw, read back through the doubled one
    assert 0.5 < float(doubled[KDA_BETA_MAX]) / 2.0 < 1.0
    # a write of more than 1 overshoots: the state it leaves is larger
    assert float(doubled[KDA_STATE_ABSMAX]) > float(plain[KDA_STATE_ABSMAX])


# -- (c) the shares add up -----------------------------------------------------

def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST, at the published counts: 32 chips with 10 of 320
    experts each, 8 a token, a router 320 wide (2.5 lane blocks: no other
    model's width): their routed parts, and the shared expert ONCE, are the
    whole layer as the reference has it; every share routes over all 320
    and counts the same assignments; the held shares sum to 1."""
    p = expert_layer(d=32, m=16, experts=320)
    n = xing4.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = xing4.swiglu(n, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    whole, chosen = xing4.expert_ffn(p["x"][None], p, k=8, factor=1.0,
                                     first=0, eps=1e-6)
    parts = shares_add_up(
        "solar", p, lambda p, first, held: share(p, first, held, 8, 1.0),
        whole[0], chosen, k=8, shared=shared)
    # one share alone is the reference's with the same experts held
    alone, _ = xing4.expert_ffn(
        p["x"][None], {**p, **{w: p[w][30:40] for w in (
            "w_gate", "w_up", "w_down")}}, k=8, factor=1.0, first=30,
        eps=1e-6)
    np.testing.assert_allclose(parts[3][0] + shared, alone[0], atol=2e-5)


# -- (d) the train step, a mesh, the configuration file ------------------------

def test_train_step_reports_the_write_strength_beside_the_rules_statistics():
    """A train step under the layer checkpoint with the flash kernels:
    ``kda_beta_max`` (over 1, under 2), ``kda_state_absmax`` and
    ``kda_chunk_decay_min`` are among the step's metrics, nothing is
    dropped and the loss falls."""
    metrics = train_step_reports("solar").metrics
    assert 1.0 < float(metrics[KDA_BETA_MAX]) < 2.0
    assert np.isfinite(float(metrics[KDA_STATE_ABSMAX]))
    assert float(metrics[KDA_CHUNK_DECAY_MIN]) < 0.0
    assert float(metrics["moe_dropped"]) == 0.0


def test_on_a_mesh_the_model_is_one_devices():
    """fsdp=2 x tp=2: the loss and the three statistics of the rule equal
    one device's (``kda_beta_max`` is a maximum over a batch the mesh
    splits; the 2 KV heads divide over ``tp``)."""
    cfg = tiny()
    params = program("solar").params
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    with HIGHEST:
        want, want_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg))(params)
        got, got_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg, mesh=mesh))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for name in (KDA_BETA_MAX, KDA_STATE_ABSMAX, KDA_CHUNK_DECAY_MIN):
        np.testing.assert_allclose(got_m[name], want_m[name], rtol=1e-5)


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                24576, 320, 10, 0, 0, 4)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.num_selected, cfg.norm_topk_prob,
            cfg.router_scoring, cfg.shared_experts, cfg.select_bias,
            cfg.routed_scaling_factor, cfg.tie_embeddings, cfg.norm_eps) == (
                4096, 64, 8, 128, 1280, 8, True, "sigmoid", 1, True, 1,
                False, 1e-5)
    assert (cfg.gqa_layers, cfg.kda_neg_eigval, cfg.attn_output_gate,
            cfg.position_embedding, cfg.rotary(False), cfg.kv_lora_rank,
            cfg.aux_loss_coef) == (
                tuple(range(0, 48, 4)), True, True, "nope", False, 0, 0.0)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_rank,
            cfg.kda_conv) == (64, 128, 8192, 128, 4)
    assert cfg.kind_runs == ((("attention", "moe"), 1), (("kda", "moe"), 3))
    assert solar_open2.kinds(conf) == cfg.layer_kinds
    assert kda_kernels_fit(cfg.kda_head_dim, cfg.kda_head_dim, 64)
    # the published depth: 12 softmax layers among 48, G K K K twelve times
    whole = LlamaConfig(**{**train.program_fields(conf), "num_layers": 48})
    assert whole.layer_runs == (("attention", 1), ("kda", 3)) * 12
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              4096)
    assert drawn.shape == (1, 4097) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 24000 < drawn.max() < 24576
