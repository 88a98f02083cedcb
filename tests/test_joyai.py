"""JoyAI-LLM-Flash's block at CPU size — latent attention on the plain
residual, sigmoid-scored experts with a selection bias, a shared expert and
a held share spread over an ``ep`` axis WITH its exchange, a predicted-ahead
module — the program (``ray_tpu/models/llama.py`` and its ops) against the
plain reference (``benchmark/reference/joyai_flash.py``) on seeded weights.
That the same model on ``MeshConfig(ep=2)`` and ``(ep=4)`` equals the
one-device program is ``tests/test_moe.py::
test_a_share_over_ep_equals_one_device``."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import xing4
from ray_tpu.ops.moe import moe_block
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import named_sharding
from ray_tpu.train.core import (
    STEP_SCOPES, default_optimizer, init_train_state, make_train_step,
    train_state_shardings)
from ray_tpu.util.tracing import scope_and_phase
import tiny_models
from tiny_models import (
    against_the_reference, expert_layer, fault_ids, program, share,
    shares_add_up, stands_apart)

tiny = functools.partial(tiny_models.tiny, "joyai")


# -- (a) the whole model against the reference, one device --------------------

def test_loss_per_token_loss_and_gradients_equal_the_plain_reference():
    assert program("joyai").cfg.kind_runs == (
        (("latent", "dense"), 1), (("latent", "moe"), 2))
    _, parts, _, ours = against_the_reference(
        "joyai", parts=("loss", "mtp_loss", "moe_held_share"))
    assert float(parts["moe_dropped"]) == 0.0
    assert float(parts["moe_rank_rows_max_over_mean"]) == 1.0  # no ranks
    # no gradient reaches a selection bias
    assert not np.any(np.asarray(ours["layers"][1]["router_bias"]))


@pytest.mark.parametrize("fault", fault_ids("joyai"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """What each part is worth to the loss (the row's ``faults``)."""
    stands_apart("joyai", fault)


# -- (c) the two hosts' shares add up -----------------------------------------

_expert_layer = functools.partial(expert_layer, experts=16)


def _block(p, first, held):
    """The routed part alone of the host that holds ``held`` experts from
    ``first`` on, its step counters beside it."""
    return share(p, first, held, 4, 2.5)


def test_the_two_hosts_shares_add_up_to_the_uncut_layer():
    """Host 0 with experts 0..7 and host 1 with 8..15: their routed parts,
    and the shared expert ONCE, are the whole layer as the reference has
    it."""
    p = _expert_layer()
    n = xing4.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = xing4.swiglu(n, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    whole, _ = xing4.expert_ffn(p["x"][None], p, k=4, factor=2.5, first=0,
                                eps=1e-6)
    shares_add_up("joyai", p, _block, whole[0], k=4, shared=shared)


# -- (e) the straggler's statistic --------------------------------------------

def _over_ep(p, mesh, first=0, held=8):
    """``_block`` with the tokens and the held experts split over the
    mesh's ``ep`` axis: the output and the statistics."""
    weights = P("ep", None, None)
    fn = jax.jit(jax.shard_map(
        lambda x, norm, router, bias, *w: moe_block(
            x, norm, router, *w, select_bias=bias, num_selected=4,
            norm_topk_prob=True, scoring="sigmoid", gate_scale=2.5,
            first_expert=first, residual=False, expert_axis="ep"),
        mesh=mesh, in_specs=(P("ep", None), P(), P(), P(), weights, weights,
                             weights),
        out_specs=(P("ep", None), P()), check_vma=False))
    return fn(p["x"], p["mlp_norm"], p["router"], p["router_bias"],
              *(p[w][first:first + held]
                for w in ("w_gate", "w_up", "w_down")))


def test_rank_rows_max_over_mean_reads_a_routing_made_uneven_by_hand():
    mesh = make_mesh(MeshConfig(ep=4), devices=jax.devices()[:4])
    p = _expert_layer()
    # as drawn: near even, and the exchange gives what one device gives
    y, stats = _over_ep(p, mesh)
    want, alone = _block(p, 0, 8)
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_array_equal(stats["counts"], alone["counts"])
    rows = np.asarray(alone["counts"][:8]).reshape(4, 2).sum(1)
    assert float(stats["rank_rows_max_over_mean"]) == pytest.approx(
        rows.max() / rows.mean())
    assert float(stats["dropped"]) == 0.0
    # every token pushed to experts 0 and 1 (rank 0's) and 14, 15 (the
    # other host's): rank 0 has all the live rows, 4 times the mean
    pushed = dict(p, router_bias=jnp.zeros(16).at[
        jnp.array([0, 1, 14, 15])].set(10.0))
    y, stats = _over_ep(pushed, mesh)
    np.testing.assert_allclose(y, _block(pushed, 0, 8)[0], atol=2e-5)
    assert float(stats["rank_rows_max_over_mean"]) == 4.0
    assert float(stats["held_share"]) == 0.5
    assert float(stats["dropped"]) == 0.0


# -- (d) outside the expert layer the ranks are data parallel -----------------

def _dot_rows(hlo: str, scopes):
    """{scope: the leading dimensions of the results of the matrix products
    the partitioned program runs under it}."""
    rows = {}
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\][^ ]* (?:dot|convolution)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or not name:
            continue
        scope = scope_and_phase(name.group(1), STEP_SCOPES)[0]
        if scope in scopes:
            rows.setdefault(scope, set()).add(
                tuple(int(n) for n in m.group(1).split(",")))
    return rows


def test_tokens_are_split_over_ep_outside_the_experts():
    """In the partitioned ``ep=4`` step of 4 rows x 40 positions the mixer,
    the dense FFN, the shared expert, the module's projection and both
    heads multiply ONE row's 40 tokens a chip, never the 4 rows' 160 (the
    heads: ``T_local`` x the host's whole slice); the expert layer alone
    sees the group's tokens, under its own scopes.  (40 and 160 are no
    width of the model.)"""
    cfg = tiny(remat=True)
    opt = default_optimizer()
    mesh = make_mesh(MeshConfig(ep=4), devices=jax.devices()[:4])
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, opt),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, train_state_shardings(cfg, opt, mesh))
    tokens = jax.ShapeDtypeStruct((4, 41), jnp.int32,
                                  sharding=named_sharding(mesh, "batch", None))
    assert tokens.sharding.spec == P(("dp", "fsdp", "ep"), None)
    hlo = make_train_step(cfg, opt, mesh=mesh).lower(
        state, {"tokens": tokens}).compile().as_text()
    outside = ("attn_qkv", "attn_out", "ffn", "mtp_in", "lm_head")
    seen = _dot_rows(hlo, outside + ("moe_experts", "moe_route"))
    assert set(outside) <= set(seen), sorted(seen)
    for scope in outside:
        # a product's result holds a chip's 40 tokens (as 1 x 40 or flat) or
        # no token at all (a weight's gradient), never the host's 4 x 40
        assert any(40 in shape for shape in seen[scope]), (scope, seen[scope])
        for shape in seen[scope]:
            assert 160 not in shape and shape[:2] != (4, 40), (scope, shape)
    assert any(128 in s for s in seen["lm_head"])       # the whole slice
    # the exchange is there, under its own scope, and nowhere else are
    # tokens gathered over the ranks
    gathers = [line for line in hlo.splitlines()
               if re.search(r"= \S+ all-gather(-start)?\(", line)]
    assert gathers and all("moe_exchange" in g for g in gathers), gathers
    assert "moe_exchange" in STEP_SCOPES
