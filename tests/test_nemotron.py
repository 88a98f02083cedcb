"""A model whose layers are ONE sub-block each at CPU size — a Mamba-2
mixer with several groups and the norm by groups, softmax attention without
a position signal at a wide GQA repeat, ungated relu^2 experts chosen by a
sigmoid with a selection bias beside a shared expert of its own width, a
held share — spelled by a pattern STRING as Nemotron-H's public file spells
it: the program (``ray_tpu/models/llama.py`` and its blocks) against the
plain reference (``benchmark/reference/nemotron_h.py``) on seeded weights
in float32."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import nemotron_h
from ray_tpu.models.blocks import FFNS, MIXERS
from ray_tpu.models.blocks.base import Ctx
from ray_tpu.models.llama import LAYER_PATTERN, init_params
from ray_tpu.ops.moe import moe_block
from ray_tpu.train.core import STEP_SCOPES, init_train_state, make_train_step
from ray_tpu.util.tracing import scope_and_phase
import tiny_models
from tiny_models import (
    ROWS, against_the_reference, apart as _apart, fault_ids, program,
    shares_add_up, stands_apart)

TOKENS = ROWS["nemotron"].tokens
tiny = functools.partial(tiny_models.tiny, "nemotron")
HIGHEST = jax.default_matmul_precision("highest")


# -- (a) the pattern string ---------------------------------------------------

def test_the_pattern_string_gives_the_layers_their_pairs():
    published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    whole = tiny(num_layers=52, layer_pattern=published)
    kinds = whole.layer_kinds
    assert len(kinds) == 52
    assert (kinds.count(("mamba", "none")), kinds.count(("none", "moe")),
            kinds.count(("attention", "none"))) == (23, 23, 6)
    # every run of this pattern is one layer long
    assert all(n == 1 for _, n in whole.kind_runs)
    cut = dataclasses.replace(whole, num_layers=9)   # its first characters
    assert [k for k, _ in cut.kind_runs] == [LAYER_PATTERN[c]
                                             for c in "MEMEM*EME"]
    assert tiny(num_layers=3, layer_pattern="--M").kind_runs == (
        (("none", "dense"), 2), (("mamba", "none"), 1))
    assert hash(cut) != hash(whole)
    with pytest.raises(ValueError, match=r"layer_pattern holds \['X'\]"):
        tiny(layer_pattern="MEXME")
    with pytest.raises(ValueError, match="num_layers"):
        tiny(layer_pattern="ME")
    with pytest.raises(ValueError, match="in place of layer_types"):
        tiny(layer_types=("mamba",) * 5)
    with pytest.raises(ValueError, match="ffn_act"):
        tiny(ffn_act="gelu")


def test_a_layer_holds_its_one_sub_blocks_tensors_and_no_other():
    cfg = tiny()
    assert cfg.kind_runs == tuple((LAYER_PATTERN[c], 1) for c in "MEM*E")
    stacks = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    assert [sorted(s) for s in stacks] == [
        sorted(MIXERS["mamba"].shapes(cfg)), sorted(FFNS["moe"].shapes(cfg)),
        sorted(MIXERS["mamba"].shapes(cfg)),
        sorted(MIXERS["attention"].shapes(cfg)),
        sorted(FFNS["moe"].shapes(cfg))]
    expert = stacks[1]
    assert "w_gate" not in expert and "shared_gate" not in expert
    assert expert["w_up"].shape == (1, 8, 64, 32)
    assert expert["shared_up"].shape == (1, 64, 48)
    assert expert["shared_down"].shape == (1, 48, 64)
    assert expert["router"].shape == (1, 64, 16)
    assert stacks[3]["wk"].shape == (1, 64, 32)


def test_the_residual_scheme_draws_what_writes_to_the_stream_smaller():
    """``rescale_prenorm_residual`` (the public files' key) scales the
    projections that write to the residual by 1/sqrt(2 x the published
    depth), whatever depth is run, ``select_bias_init`` the selection
    bias's draw, and both leave every other tensor's draw as it was."""
    cfg = tiny()
    assert cfg.residual_init_scale == 1.0
    assert dataclasses.replace(
        cfg, rescale_prenorm_residual=True).residual_init_scale == 10 ** -0.5
    plain = init_params(jax.random.PRNGKey(2), cfg)["layers"]
    scaled = init_params(jax.random.PRNGKey(2), dataclasses.replace(
        cfg, rescale_prenorm_residual=True, published_layers=8,
        select_bias_init=0.005))["layers"]
    factor = {"ssm_out": 0.25, "wo": 0.25, "w_down": 0.25,
              "shared_down": 0.25, "router_bias": 0.25}
    seen = set()
    for was, now in zip(plain, scaled):
        for name, w in was.items():
            np.testing.assert_allclose(
                now[name], w * factor.get(name, 1.0), rtol=1e-6, atol=0)
            seen.add(name)
    assert set(factor) <= seen
    assert float(jnp.std(plain[1]["router_bias"])) == pytest.approx(
        0.02, rel=0.3)


# -- (b) the whole model against the reference --------------------------------

def test_loss_per_token_loss_and_gradients_equal_the_plain_reference():
    _, parts, _, ours = against_the_reference(
        "nemotron", parts=("loss", "moe_held_share"), grad_rtol=2e-4)
    assert float(parts["moe_dropped"]) == 0.0
    assert 0.3 < float(parts["moe_held_share"]) < 0.7   # half are held
    # every tensor has a gradient but the selection bias, which none reaches
    for stack in ours["layers"]:
        for name, g in stack.items():
            assert bool(jnp.any(g != 0)) == (name != "router_bias"), name


def test_the_kernels_under_the_checkpoint_give_the_same_loss_and_gradients():
    """The same model as a chip runs it — the flash kernel and the grouped
    kernels interpreted, the layer checkpoint on — against the plain XLA
    forms of the test above."""
    params = program("nemotron").params
    (want, _), want_g = program("nemotron").value_and_grad(params)
    (got, _), got_g = program("nemotron", attn_impl="flash",
                              remat=True).value_and_grad(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    apart = _apart(got_g, want_g)
    assert max(jax.tree.leaves(apart)) < 1e-4, apart


@pytest.mark.parametrize("fault", fault_ids("nemotron"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """The faults the chip check is shown to catch (PERF.md section 6; the
    row's ``faults``), at CPU size and in float32: the per-token losses of
    the program with the part changed stand apart from the reference's by
    a hundred times what the sound program's do (3e-5 at most, the test
    above)."""
    stands_apart("nemotron", fault)


# -- (c) the eight shares add up ----------------------------------------------

def _expert_layer(tokens=96, d=64, m=32, shared=48, experts=16, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal
    return dict(
        x=normal(keys[0], (tokens, d)),
        mlp_norm=1.0 + 0.3 * normal(keys[1], (d,)),
        router=normal(keys[2], (d, experts)) * d ** -0.5,
        router_bias=0.05 * normal(keys[3], (experts,)),
        w_up=normal(keys[4], (experts, d, m)) * d ** -0.5,
        w_down=normal(keys[5], (experts, m, d)) * m ** -0.5,
        shared_up=normal(keys[6], (d, shared)) * d ** -0.5,
        shared_down=normal(keys[7], (shared, d)) * shared ** -0.5)


@functools.partial(jax.jit, static_argnums=2)
def _share(p, first, held):
    """The routed part alone of the chip that holds ``held`` experts from
    ``first`` on, its step counters beside it; one program, ``first``
    traced."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], None,
        *(jax.lax.dynamic_slice_in_dim(p[w], first, held)
          for w in ("w_up", "w_down")),
        num_selected=3, norm_topk_prob=True, topk_norm_eps=1e-20,
        scoring="sigmoid", select_bias=p["router_bias"], gate_scale=2.5,
        first_expert=first, residual=False)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Chips 0..7 with two experts each: their routed parts, and the shared
    expert ONCE, are the whole layer as the reference has it."""
    p = _expert_layer()
    h = nemotron_h.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = nemotron_h.relu2(h, p["shared_up"], p["shared_down"])
    with HIGHEST:
        whole, _ = nemotron_h.expert_ffn(h[None], p, k=3, factor=2.5,
                                         first=0)
    shares_add_up("nemotron", p, _share, whole[0], k=3, shared=shared,
                  atol=3e-5)


# -- (d) the scopes of a layer that is one sub-block --------------------------

def _scopes_of_a_step(cfg):
    opt = optax.adam(1e-2)
    state = jax.eval_shape(
        lambda k: init_train_state(k, cfg, opt), jax.random.PRNGKey(0))
    text = make_train_step(cfg, opt).lower(
        state, {"tokens": TOKENS}).as_text(debug_info=True)
    return {scope_and_phase(n, STEP_SCOPES)[0]
            for n in re.findall(r'loc\("([^"]*)"', text)}


@pytest.mark.parametrize("pattern,opened", [
    ("MM", MIXERS["mamba"].scopes),
    ("**", MIXERS["attention"].scopes),
    # (the exchange over an ``ep`` axis alone; the latent's pair where the
    # experts work in one: tests/test_nemotron3.py)
    ("EE", set(FFNS["moe"].scopes) - {"moe_exchange", "moe_latent"}),
    ("--", FFNS["dense"].scopes)], ids=["M", "attention", "E", "dense"])
def test_a_layer_opens_its_own_sub_blocks_scopes_alone(pattern, opened):
    """A model of ``M`` layers has no time under ``ffn`` or ``attn_*``, one
    of ``E`` layers none under a mixer's scopes: the absent half of a layer
    opens nothing."""
    seen = _scopes_of_a_step(tiny(num_layers=2, layer_pattern=pattern,
                                  remat=True, attn_impl="flash"))
    assert seen - {None, "scan"} == {
        "embed", *opened, "lm_head", "loss", "optimizer"}


@pytest.mark.parametrize("role", ["mixer", "ffn"])
def test_the_empty_block_runs_not_one_operation(role):
    """Nothing unscoped can come from it: its jaxpr is empty."""
    block = (MIXERS if role == "mixer" else FFNS)["none"]
    cfg = tiny()
    assert block.shapes(cfg) == {} and block.stats(cfg) == {}
    assert block.saved == () and block.scopes == ()
    ctx = Ctx(cfg, None, lambda x, axes: x, False)
    x = jnp.ones((2, 8, 64))
    jaxpr = jax.make_jaxpr(lambda x, aux: block.apply(ctx, x, aux, {}))(
        x, jnp.zeros(()))
    assert jaxpr.eqns == []
    out = block.apply(ctx, x, 0.0, {})
    assert out[0] is x and len(out) == (2 if role == "mixer" else 3)
    alone = block.apply(ctx, x, 0.0, {}, residual=False)[0]
    assert not np.any(np.asarray(alone))
