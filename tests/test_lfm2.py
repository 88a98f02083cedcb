"""LFM2-MoE's mechanisms at CPU size in float32: the gated short
convolution (``ops/ssm.py::gated_short_conv``), its mixer beside softmax
attention with a QK-norm a head, sigmoid-scored experts with a selection
bias behind leading dense layers, a tied head over a vocabulary slice and
a held share — the program (``ray_tpu/models/llama.py`` and its ops)
against the benchmark's plain reference (``benchmark/reference/
lfm2_moe.py``: nothing shared with the code under test) on seeded
weights."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import lfm2_moe
from ray_tpu.models.llama import (
    init_params, loss_and_counts, param_logical_axes, update_router_bias)
from ray_tpu.ops.moe import moe_block
from ray_tpu.ops.ssm import causal_conv1d, gated_short_conv
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.core import (
    STEP_SCOPES, default_optimizer, init_train_state, make_train_step)
import tiny_models
from tiny_models import (
    LFM2_PATTERN as PATTERN, ROWS, SCONV_SCOPES, against_the_reference,
    apart as _apart, fault_ids, program, shares_add_up, stands_apart,
    train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-8b-a1b-1of2"
TOKENS = ROWS["lfm2"].tokens
tiny = functools.partial(tiny_models.tiny, "lfm2")
# half the depth where the pattern itself is not what is tested: four runs
# (conv, dense), (attention, moe), (conv, moe) x 2 compile in half the time
SHALLOW = dict(num_layers=4, layer_types=PATTERN[1:5], leading_dense=1)


# -- the op --------------------------------------------------------------------

def _op_inputs(seq, width, d=8, batch=2, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(batch, seq, 3 * d)), dtype),
            jnp.asarray(rng.normal(size=(width, d)), jnp.float32))


def _token_loop(bcx, weight):
    """``y_t = C_t * sum_i w_i (B x)_(t - (k-1) + i)``, a token and a tap
    at a time in numpy float64: nothing before the sequence."""
    bcx, weight = np.asarray(bcx, np.float64), np.asarray(weight, np.float64)
    d, k = weight.shape[1], weight.shape[0]
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = gate_in * x
    y = np.zeros_like(z)
    for t in range(z.shape[1]):
        for i in range(k):
            if t - (k - 1) + i >= 0:
                y[:, t] += weight[i] * z[:, t - (k - 1) + i]
    return gate_out * y


def _plain(bcx, weight):
    """The op as plain ``jax.numpy`` for autodiff: shifted products."""
    d, k, s = weight.shape[1], weight.shape[0], bcx.shape[1]
    z = jnp.pad(bcx[..., :d] * bcx[..., 2 * d:], ((0, 0), (k - 1, 0), (0, 0)))
    return bcx[..., d:2 * d] * sum(
        weight[i] * z[:, i:i + s] for i in range(k))


OP_SHAPES = [(12, 3), (12, 4), (2, 3), (1, 4), (33, 3)]


@pytest.mark.parametrize("seq,width", OP_SHAPES)
def test_gated_short_conv_equals_a_token_by_token_loop(seq, width):
    bcx, weight = _op_inputs(seq, width)
    got = gated_short_conv(bcx, weight)
    assert got.shape == (2, seq, 8) and got.dtype == bcx.dtype
    np.testing.assert_allclose(got, _token_loop(bcx, weight), atol=1e-5)


@pytest.mark.parametrize("seq,width", OP_SHAPES)
def test_the_written_backward_equals_autodiff_of_the_plain_form(seq, width):
    bcx, weight = _op_inputs(seq, width, seed=1)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=(2, seq, 8)),
                        jnp.float32)
    ours = jax.grad(lambda *a: jnp.sum(gated_short_conv(*a) * probe),
                    argnums=(0, 1))(bcx, weight)
    theirs = jax.grad(lambda *a: jnp.sum(_plain(*a) * probe),
                      argnums=(0, 1))(bcx, weight)
    for a, b in zip(ours, theirs):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_bfloat16_goes_in_and_out_and_float32_runs_inside():
    bcx, weight = _op_inputs(64, 3, d=16, dtype=jnp.bfloat16)
    weight = weight.astype(jnp.bfloat16)
    got = gated_short_conv(bcx, weight)
    assert got.dtype == jnp.bfloat16
    exact = _token_loop(bcx.astype(jnp.float32), weight.astype(jnp.float32))
    # one rounding of the result, none inside: half a unit in the last place
    np.testing.assert_allclose(got.astype(jnp.float32), exact,
                               rtol=2 ** -8, atol=1e-6)
    d_bcx, d_w = jax.grad(
        lambda *a: jnp.sum(gated_short_conv(*a).astype(jnp.float32)),
        argnums=(0, 1))(bcx, weight)
    assert d_bcx.dtype == jnp.bfloat16 and d_w.dtype == jnp.bfloat16
    want = jax.grad(lambda *a: jnp.sum(_plain(*a)), argnums=(0, 1))(
        bcx.astype(jnp.float32), weight.astype(jnp.float32))
    np.testing.assert_allclose(d_bcx.astype(jnp.float32), want[0],
                               rtol=2 ** -7, atol=1e-5)
    np.testing.assert_allclose(d_w.astype(jnp.float32), want[1],
                               rtol=2 ** -7, atol=1e-2)


def test_causal_conv1d_keeps_its_values_and_gradients():
    """The SiLU-fused convolution shares ``_conv_pre`` and the gradients'
    helper with the gated one: still the plain form's, with and without a
    bias."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 10, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    for bias in (None, jnp.asarray(rng.normal(size=(6,)), jnp.float32)):
        def plain(x, w):
            padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
            pre = sum(w[i] * padded[:, i:i + 10] for i in range(4))
            return jax.nn.silu(pre if bias is None else pre + bias)

        np.testing.assert_allclose(causal_conv1d(x, w, bias), plain(x, w),
                                   atol=1e-6)
        ours = jax.grad(lambda *a: jnp.sum(causal_conv1d(*a, bias) ** 2),
                        argnums=(0, 1))(x, w)
        theirs = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2),
                          argnums=(0, 1))(x, w)
        for a, b in zip(ours, theirs):
            np.testing.assert_allclose(a, b, atol=2e-5)


# -- the model -----------------------------------------------------------------

def test_five_runs_that_differ_in_mixer_and_ffn_hold_only_their_kind():
    cfg = tiny()
    assert cfg.kind_runs == (
        (("conv", "dense"), 2), (("full_attention", "moe"), 1),
        (("conv", "moe"), 3), (("full_attention", "moe"), 1),
        (("conv", "moe"), 1))
    params = init_params(jax.random.PRNGKey(0), cfg)
    runs = params["layers"]
    assert len(runs) == 5 and "lm_head" not in params
    assert runs[0]["sconv_in"].shape == (2, 64, 192)
    assert runs[0]["sconv_w"].shape == (2, 3, 64)
    assert runs[0]["w_gate"].shape == (2, 64, 96) and "router" not in runs[0]
    assert "wq" not in runs[0] and "sconv_in" not in runs[1]
    assert runs[1]["q_norm"].shape == (1, 16) == runs[1]["k_norm"].shape
    assert runs[2]["w_gate"].shape == (3, 4, 64, 32)       # the 4 held
    assert runs[2]["router"].shape == (3, 64, 8)           # ALL the experts
    assert runs[2]["router_bias"].dtype == jnp.float32
    assert float(jnp.std(runs[2]["router_bias"])) > 0.0    # not left at 0
    assert float(jnp.max(jnp.abs(runs[0]["sconv_w"]))) <= 3 ** -0.5
    axes = param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(x, (str, type(None))) for x in a)))
    assert axes["layers"][0]["sconv_in"] == (
        "layer", "kernel_in", "sconv_inner")
    with pytest.raises(ValueError, match="one of the two"):
        tiny(qk_norm=True)


def test_loss_parts_and_gradients_equal_the_plain_reference():
    """Tolerances: both sides are float32, the program at XLA's default
    matmul precision on the CPU (float32) and the reference at "highest";
    what is left is the order of sums — 2e-5 relative on means of 66
    tokens, 3e-5 nats on one token's loss, 1e-4 of a gradient's largest
    entry (the selection is discrete: a swapped expert would read 1e-2
    and more)."""
    _, parts, want, ours = against_the_reference("lfm2")
    np.testing.assert_allclose(parts["moe_held_share"],
                               want["moe_held_share"], rtol=1e-6)
    assert 0.3 < float(parts["moe_held_share"]) < 0.7
    assert float(parts["moe_dropped"]) == 0.0
    assert len(want["experts"]) == 6
    # no gradient reaches a selection bias; the tied table gets both uses'
    assert not np.any(np.asarray(ours["layers"][1]["router_bias"]))
    assert np.any(np.asarray(ours["embed"]))


def test_the_checkpoint_and_the_flash_kernels_give_the_same_loss():
    shallow, under_remat = (program("lfm2", **SHALLOW, **kw)
                            for kw in ({}, dict(remat=True)))
    params = shallow.params
    (plain, _), g_plain = shallow.value_and_grad(params)
    (remat, _), g_remat = under_remat.value_and_grad(params)
    np.testing.assert_allclose(remat, plain, rtol=1e-6)
    flash, _ = program("lfm2", **SHALLOW, attn_impl="flash").loss(params)
    np.testing.assert_allclose(flash, plain, rtol=2e-5)
    assert max(jax.tree.leaves(_apart(g_remat, g_plain))) < 1e-5


@pytest.mark.parametrize("fault", fault_ids("lfm2"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """Each structural point of the configuration, got wrong in the
    program (the row's ``faults``), stands apart from the reference by more
    than the chip check's tolerance of the mean (3e-4), but for the
    ``1e-6`` of the renormalisation, which moves a gate by a millionth and
    which no check can see: the row says so rather than claim it."""
    stands_apart("lfm2", fault)


# -- the share -----------------------------------------------------------------

def _expert_layer(seed=3, tokens=96, d=32, m=16, experts=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    return dict(
        x=normal(keys[0], (tokens, d)),
        mlp_norm=1.0 + 0.3 * normal(keys[1], (d,)),
        router=normal(keys[2], (d, experts)) * d ** -0.5,
        router_bias=0.05 * normal(keys[3], (experts,)),
        w_gate=normal(keys[4], (experts, d, m)) * d ** -0.5,
        w_up=normal(keys[5], (experts, d, m)) * d ** -0.5,
        w_down=normal(keys[0], (experts, m, d)) * m ** -0.5)


@functools.partial(jax.jit, static_argnums=2)
def _share(p, first, held):
    """What the chip that holds ``held`` experts from ``first`` on adds,
    its step counters beside it; one program, ``first`` traced."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=4, norm_eps=1e-5, norm_topk_prob=True,
        topk_norm_eps=1e-6, scoring="sigmoid", select_bias=p["router_bias"],
        first_expert=first, residual=False)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """2 chips with 4 of 8 experts each (``first_expert`` 0 and half):
    their parts are the whole layer as the reference has it — no shared
    expert to count once."""
    p = _expert_layer()
    h = lfm2_moe.rms_norm(p["x"], p["mlp_norm"], 1e-5)
    whole, chosen = lfm2_moe.expert_ffn(h[None], p, k=4, factor=1.0, first=0)
    parts = shares_add_up("lfm2", p, _share, whole[0], chosen, k=4)
    # one share alone is the reference's with the same experts held
    alone, _ = lfm2_moe.expert_ffn(
        h[None], {**p, **{w: p[w][4:] for w in ("w_gate", "w_up", "w_down")}},
        k=4, factor=1.0, first=4)
    np.testing.assert_allclose(parts[1][0], alone[0], atol=2e-5)
    # the gates sum to 1 less the guard's millionth
    gates, _ = lfm2_moe.route(h, p["router"], p["router_bias"], 4, 1.0)
    total = np.asarray(jnp.sum(gates, -1))
    assert np.all(total < 1.0) and np.all(total > 1.0 - 2e-6)


# -- the train step ------------------------------------------------------------

def test_update_router_bias_moves_the_bias_of_every_expert_run():
    """Five runs, three kinds: the two dense runs hand out no counts and
    are handed on as they are; each expert run's bias moves by the rule
    from its OLD value, whatever the optimizer made of it."""
    cfg = tiny()
    old = init_params(jax.random.PRNGKey(0), cfg)
    _, (_, counts) = jax.jit(lambda p: loss_and_counts(
        p, {"tokens": TOKENS}, cfg))(old)
    assert counts["layers"][0] is None and counts["mtp"] is None
    assert [None if c is None else c.shape for c in counts["layers"]] == [
        None, (1, 8), (3, 8), (1, 8), (1, 8)]
    new = jax.tree.map(lambda a: a + 1.0, old)   # an optimizer's step
    moved = update_router_bias(old, new, counts, cfg)
    assert moved["layers"][0] is new["layers"][0]
    for run in (1, 2, 3, 4):
        step = np.asarray(moved["layers"][run]["router_bias"]
                          - old["layers"][run]["router_bias"])
        over = np.asarray(counts["layers"][run]) - 64 * 4 / 8
        np.testing.assert_allclose(step, -0.001 * np.sign(over), atol=1e-7)
        assert moved["layers"][run]["router"] is new["layers"][run]["router"]


def test_the_train_step_reports_the_scopes_and_the_counters():
    assert set(SCONV_SCOPES) <= set(STEP_SCOPES)
    stepped = train_step_reports("lfm2")
    assert float(stepped.metrics["moe_dropped"]) == 0.0
    for run in (1, 2, 3, 4):
        moved = np.asarray(stepped.state.params["layers"][run][
            "router_bias"]) - stepped.before["layers"][run]["router_bias"]
        assert np.all((moved == 0) | np.isclose(np.abs(moved), 0.001,
                                                atol=1e-6))


def test_the_model_trains_on_a_mesh_as_on_one_device():
    """fsdp=2 x tp=2 (the conv mixer's inner width on no mesh axis): the
    loss of the sharded step is the one-device step's."""
    cfg = tiny(num_kv_heads=4, experts_held=0, first_expert=0, **SHALLOW)
    opt = default_optimizer()
    batch = {"tokens": jnp.tile(TOKENS, (2, 1))}
    one = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    _, want = make_train_step(cfg, opt, donate=False)(one, batch)
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh=mesh)
    _, got = make_train_step(cfg, opt, mesh=mesh, donate=False)(state, batch)
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=2e-5)


# -- the configuration file ----------------------------------------------------

def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_experts, cfg.experts_held,
            cfg.first_expert) == (32768, 32, 16, 0)
    assert (cfg.head_dim, cfg.sconv_width, cfg.qk_head_norm, cfg.qk_norm,
            cfg.topk_norm_eps, cfg.tie_embeddings) == (
                64, 3, True, False, 1e-6, True)
    assert cfg.select_bias and cfg.router_scoring == "sigmoid"
    assert [n for _, n in cfg.kind_runs] == [2, 1, 3, 1, 1]
    assert lfm2_moe.kinds(conf) == cfg.layer_kinds
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 2,
                              8192)
    assert drawn.shape == (2, 8193) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 32000 < drawn.max() < 32768
