"""Kimi-Linear's block at CPU size — the delta rule with a decay PER KEY
CHANNEL (``ops/delta.py::kda_chunked``, the mixer ``kda``) three layers to
one of latent attention WITHOUT a q rank or a rotation, sigmoid-scored
experts with a selection bias, a shared expert and a held share — the
program (``ray_tpu/models/llama.py`` and its blocks) against the plain
reference (``benchmark/reference/kimi_linear.py``: the recurrence a token
at a time, nothing shared with the code under test) on seeded weights, in
float32.  The rule itself and its kernels: ``tests/test_kda.py``."""

import functools
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import kimi_linear, xing4
from ray_tpu.models.blocks import attention, kda
from ray_tpu.models.blocks.kda import KDA_CHUNK_DECAY_MIN, KDA_STATE_ABSMAX
from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
from ray_tpu.ops.delta import kda_kernels_fit
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.core import STEP_SCOPES
from ray_tpu.util.tracing import scope_and_phase
import tiny_models
from tiny_models import (
    KDA_SCOPES, against_the_reference, expert_layer, fault_ids, program,
    share, shares_add_up, stands_apart, train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b-1of16"
HIGHEST = jax.default_matmul_precision("highest")
TOKENS = tiny_models.ROWS["kimi"].tokens
tiny = functools.partial(tiny_models.tiny, "kimi")


# -- (b) the whole model against the reference ---------------------------------

@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(impl):
    """K K K F K with a dense first layer through ``loss_fn`` — with the
    XLA attention, and with the flash kernels (192 / 128 wide, interpreted)
    under the layer checkpoint — against the benchmark's reference, which
    computes the recurrence a token at a time: the loss within 2e-6
    (relative), each position's loss within 3e-5 nats, every gradient leaf
    within 5e-4 of its scale (float32 against float32 in another order of
    sums: the chunk's inverse and the levels' products)."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    assert program("kimi", **kw).cfg.kind_runs == (
        (("kda", "dense"), 1), (("kda", "moe"), 2), (("latent", "moe"), 1),
        (("kda", "moe"), 1))
    _, parts, _, ours = against_the_reference(
        "kimi", parts=("loss", "moe_held_share"), rtol=2e-6, nll_atol=3e-5,
        grad_rtol=5e-4, **kw)
    assert float(parts["moe_dropped"]) == 0.0
    assert 0.05 < float(parts[KDA_STATE_ABSMAX]) < 100.0
    assert float(parts[KDA_CHUNK_DECAY_MIN]) < 0.0
    assert set(kimi_linear.STEP_METRICS) <= set(parts)
    # no gradient reaches a selection bias
    assert not np.any(np.asarray(ours["layers"][1]["router_bias"]))


@pytest.mark.parametrize("fault", fault_ids("kimi"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """What each part is worth to the loss (the row's ``faults``): a
    rotation of the 64 shared columns, each part of the gates, the other
    chip's experts; and, inside the KDA mixer, a SiLU where the output
    gate's sigmoid is, beta in (0, 2), ONE decay a head (the mean over its
    channels: the rule Olmo-Hybrid has), a latent layer where the lists
    name a KDA one."""
    stands_apart("kimi", fault)


# -- (c) the latent mixer without a q rank; the older trees as they were -------

def test_the_latent_mixer_takes_its_q_tensors_from_the_q_rank():
    """``q_lora_rank`` null or 0: ONE matrix ``wq`` of heads x (nope +
    rope) columns; a number: ``wq_a``, ``q_a_norm``, ``wq_b`` in the order
    they always stood in (Xing4's and JoyAI's trees, byte for byte:
    ``tests/test_blocks.py`` pins both whole)."""
    with_rank = list(attention.LATENT.shapes(tiny_models.tiny("joyai")))
    assert with_rank == ["attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                         "kv_a_norm", "wkv_b", "wo"]
    for rank in (None, 0):
        shapes = attention.LATENT.shapes(tiny(q_lora_rank=rank))
        assert list(shapes) == ["attn_norm", "wq", "wkv_a", "kv_a_norm",
                                "wkv_b", "wo"]
        assert shapes["wq"].shape == (64, 4 * 24)
        assert shapes["wq"].axes == ("layer", "kernel_in", "heads")
    # an older model's stack starts with the tensors it always started with
    joyai = init_params(jax.random.PRNGKey(0), tiny_models.tiny("joyai"))
    assert list(joyai["layers"][0])[:4] == ["attn_norm", "wq_a", "q_a_norm",
                                            "wq_b"]


def test_without_rotation_the_program_holds_no_rotary_op():
    """``position_embedding`` ``nope``: the latent layer's program has no
    ``rope`` scope, no cos and no sin; under ``rope`` it has all three."""
    def text(**kw):
        cfg = tiny(**kw)
        return jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg)[0]
                       ).lower(program("kimi").params).as_text(
                           debug_info=True)

    plain, rotated = text(), text(position_embedding="rope")
    assert "/rope/" not in plain and "cosine" not in plain
    assert "/rope/" in rotated and "cosine" in rotated


def test_the_lists_name_the_layers_from_one():
    cfg = tiny()
    assert [m for m, _ in cfg.layer_kinds] == ["kda"] * 3 + ["latent", "kda"]
    assert [f for _, f in cfg.layer_kinds] == ["dense"] + ["moe"] * 4
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_rank,
            cfg.kda_conv) == (4, 16, 64, 16, 4)
    assert kimi_linear.kinds(tiny_models.ROWS["kimi"].conf) == cfg.layer_kinds
    assert hash(cfg) == hash(tiny())            # the nested group is frozen
    eight = tiny(num_layers=8)
    assert [m for m, _ in eight.layer_kinds] == ["kda"] * 3 + [
        "latent"] + ["kda"] * 3 + ["latent"]
    with pytest.raises(ValueError, match="names every layer's mixer once"):
        tiny(num_layers=9)                      # layer 9 is in neither list
    with pytest.raises(ValueError, match="names every layer's mixer once"):
        tiny(kv_lora_rank=0)                    # a latent layer with no rank
    with pytest.raises(ValueError, match="takes its heads"):
        LlamaConfig.tiny(layer_types=("kda", "kda"))
    # with layer_types the group gives the sizes alone
    named = LlamaConfig.tiny(layer_types=("kda", "attention"),
                             linear_attn_config={"num_heads": 2,
                                                 "head_dim": 8})
    assert named.layer_runs == (("kda", 1), ("attention", 1))
    assert (named.kda_inner, named.kda_conv) == (16, 4)


# -- (d) the shares add up -----------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 chips with 2 of 32 experts each (the file's 16 chips a layer):
    their routed parts, and the shared expert ONCE, are the whole layer as
    the reference has it; every share routes over all 32 and counts the
    same assignments; the held shares sum to 1."""
    p = expert_layer()
    n = xing4.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = xing4.swiglu(n, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    whole, chosen = xing4.expert_ffn(p["x"][None], p, k=4, factor=2.446,
                                     first=0, eps=1e-6)
    parts = shares_add_up(
        "kimi", p, lambda p, first, held: share(p, first, held, 4, 2.446),
        whole[0], chosen, k=4, shared=shared)
    # one share alone is the reference's with the same experts held
    alone, _ = xing4.expert_ffn(
        p["x"][None], {**p, **{w: p[w][6:8] for w in (
            "w_gate", "w_up", "w_down")}}, k=4, factor=2.446, first=6,
        eps=1e-6)
    np.testing.assert_allclose(parts[3][0] + shared, alone[0], atol=2e-5)


# -- (e) the train step, a mesh, the configuration file ------------------------

def test_train_step_reports_the_rule_and_names_its_scopes():
    """A train step under the layer checkpoint: ``kda_state_absmax`` and
    ``kda_chunk_decay_min`` are among the step's metrics, the loss falls,
    and the four ``kda_*`` scopes — members of ``STEP_SCOPES`` — are on the
    compiled program's ops in the forward, the rematerialised and the
    backward pass, except ``kda_in``'s rematerialised matmul: the
    checkpoint keeps the projection by name (and, where the Pallas pair
    runs — not at this row's heads of 16 —, the kernel's output, the pairs'
    inverses and a state a grid step: ``tests/test_blocks.py``)."""
    from ray_tpu.util.tracing import KERNEL_NAMES

    assert set(KDA_SCOPES) <= set(STEP_SCOPES)
    # the kernels' prefix is a row of ``step-breakdown`` and no scope's name
    assert "kdarule_" in KERNEL_NAMES and not any(
        s.startswith("kdarule_") for s in STEP_SCOPES)
    assert kda.BLOCK.saved == ("kda_proj", "kda_rule_out",
                               "kda_rule_inverse", "kda_rule_entering")
    stepped = train_step_reports("kimi")
    assert np.isfinite(float(stepped.metrics[KDA_STATE_ABSMAX]))
    assert float(stepped.metrics[KDA_CHUNK_DECAY_MIN]) < 0.0
    assert float(stepped.metrics["moe_dropped"]) == 0.0
    dots = {scope_and_phase(n, STEP_SCOPES) for n in re.findall(
        r'dot\([^\n]*op_name="([^"]*)"', stepped.compiled.as_text())}
    assert ("kda_in", "forward") in dots and ("kda_in", "backward") in dots
    assert ("kda_in", "remat") not in dots


def test_on_a_mesh_the_rule_runs_per_shard_of_the_batch():
    """fsdp=2 x tp=2: the loss, the state's maximum and the decay's minimum
    equal one device's (the rule inside a manual region, rows over the data
    axes, both statistics joined over the shards)."""
    cfg = tiny()
    params = program("kimi").params
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    with HIGHEST:
        want, want_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg))(params)
        got, got_m = jax.jit(lambda p: loss_fn(
            p, {"tokens": TOKENS}, cfg, mesh=mesh))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for name in (KDA_STATE_ABSMAX, KDA_CHUNK_DECAY_MIN):
        np.testing.assert_allclose(got_m[name], want_m[name], rtol=1e-5)


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert, cfg.leading_dense, cfg.num_layers) == (
                20480, 256, 16, 0, 1, 8)
    assert (cfg.embed_dim, cfg.num_heads, cfg.head_dim, cfg.mlp_dim,
            cfg.dense_width, cfg.num_selected, cfg.norm_topk_prob,
            cfg.router_scoring, cfg.shared_experts, cfg.select_bias,
            cfg.routed_scaling_factor, cfg.tie_embeddings, cfg.norm_eps) == (
                2304, 32, 128, 1024, 9216, 8, True, "sigmoid", 1, True,
                2.446, False, 1e-5)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.position_embedding,
            cfg.rotary(False), cfg.num_nextn, cfg.aux_loss_coef) == (
                None, 512, 128, 64, 128, "nope", False, 0, 0.0)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_rank,
            cfg.kda_conv) == (32, 128, 4096, 128, 4)
    assert cfg.kind_runs == (
        (("kda", "dense"), 1), (("kda", "moe"), 2), (("latent", "moe"), 1),
        (("kda", "moe"), 3), (("latent", "moe"), 1))
    assert kimi_linear.kinds(conf) == cfg.layer_kinds
    assert kda_kernels_fit(cfg.kda_head_dim, cfg.kda_head_dim, 64)
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              8192)
    assert drawn.shape == (1, 8193) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 20000 < drawn.max() < 20480
