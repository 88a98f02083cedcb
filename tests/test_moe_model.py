"""An OLMoE-shaped model through ``loss_fn`` against the benchmark's plain
reference, and the expert layer over an ``ep`` axis against one device.
CPU, float32 unless said; the Pallas kernels run in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import tiny_models
from benchmark.reference import olmoe
from ray_tpu.models import init_params, loss_fn, param_logical_axes
from ray_tpu.parallel import MeshConfig, make_mesh, shard_pytree, use_mesh
from tiny_models import ROWS, against_the_reference, program, reference

CONF = ROWS["olmoe"].conf
_tiny_olmoe = functools.partial(tiny_models.tiny, "olmoe")


def _params_and_tokens(cfg, rows=2, seq=64):
    return ROWS["olmoe"].params(cfg), jax.random.randint(
        jax.random.PRNGKey(1), (rows, seq + 1), 0, cfg.vocab_size)


def test_tiny_olmoe_equals_the_plain_reference():
    _, metrics, _, _ = against_the_reference(
        "olmoe", parts=("loss", "aux_loss", "z_loss"), rtol=2e-6,
        nll_atol=None, grad_rtol=1e-5)
    assert float(metrics["moe_dropped"]) == 0
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 8 / 3


@pytest.mark.parametrize("left_out", ["qk_norm", "z_loss", "one_expert",
                                      "aux_loss", "renormalised"])
def test_reference_check_fails_when_part_of_the_layer_is_left_out(left_out):
    """What the benchmark's check (relative ``LOSS_RTOL``) must catch."""
    broken = {"qk_norm": dict(qk_norm=False),
              "z_loss": dict(z_loss_coef=0.0),
              "aux_loss": dict(aux_loss_coef=0.0),
              "one_expert": dict(num_selected=2),
              "renormalised": dict(norm_topk_prob=True)}[left_out]
    params = program("olmoe").params
    want = float(reference("olmoe").parts["total"])
    got = float(program("olmoe", **broken).loss(params)[0])
    assert abs(got - want) > 10 * olmoe.LOSS_RTOL * want, (got, want)


def test_bfloat16_inside_the_stated_tolerance():
    """bfloat16 parameters and activations against the float32 reference
    on the same (bfloat16) parameters, 2048 tokens."""
    cfg = _tiny_olmoe(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                      max_seq_len=512)
    params, tokens = _params_and_tokens(cfg, rows=4, seq=512)
    got = float(jax.jit(lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0])(
        params))
    want = float(jax.jit(lambda p: olmoe.loss(p, tokens, CONF))(params))
    assert abs(got - want) <= olmoe.loss_rtol(4 * 512) * want, (got, want)


@functools.lru_cache(maxsize=None)
def _olmoe_on_one_device():
    """Four rows of the tiny OLMoE, and its loss, metrics and gradients on
    one device: once for every mesh."""
    cfg = _tiny_olmoe()
    params, tokens = _params_and_tokens(cfg, rows=4)
    loss = lambda p, t, mesh=None: loss_fn(p, {"tokens": t}, cfg, mesh=mesh)
    return cfg, params, tokens, loss, jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params, tokens)


@pytest.mark.parametrize("mesh_kw", [dict(ep=2), dict(ep=4), dict(dp=2, ep=2,
                                                                  tp=2)],
                         ids=["ep2", "ep4", "dp2_ep2_tp2"])
def test_expert_parallel_equals_one_device(mesh_kw):
    cfg, params, tokens, loss, ((want, m1), g1) = _olmoe_on_one_device()
    n = int(np.prod(list(mesh_kw.values())))
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:n])
    with use_mesh(mesh):
        sharded = shard_pytree(params, param_logical_axes(cfg), mesh)
        toks = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        (got, m2), g2 = jax.jit(jax.value_and_grad(
            functools.partial(loss, mesh=mesh), has_aux=True))(sharded, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("aux_loss", "z_loss", "moe_load_max_over_mean"):
        assert float(m2[name]) == pytest.approx(float(m1[name]), rel=1e-5)
    assert float(m2["moe_dropped"]) == 0
    # an ``ep`` rank is a share and takes the token-side sum over its live
    # rows (one trip over a shard's t * 3 slots, and t rows at the ends);
    # one device holds every expert and gathers a row a (token, choice)
    t = 4 * 64 // mesh_kw.get("dp", 1)
    assert float(m1["moe_token_rows_read_share"]) == 1.0
    assert float(m2["moe_token_rows_read_share"]) == pytest.approx(
        (t * 3 + 2 + t) / (t * 3))
    worst = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
        jax.device_get(g2), g1)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst


@functools.lru_cache(maxsize=None)
def _joyai_on_one_device():
    """The tiny JoyAI model (at ``LlamaConfig.tiny``'s vocabulary and RoPE
    base, as this test has always run it) under the flash kernels and the
    checkpoint, and its loss, counters and gradients on one device: once
    for every ``ep``."""
    from ray_tpu.models.llama import loss_and_counts
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    cfg = tiny_models.tiny("joyai", vocab_size=256, rope_theta=10000.0,
                           attn_impl="flash", remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                cfg.vocab_size)
    loss = lambda p, t, mesh=None: loss_and_counts(
        p, {"tokens": t}, cfg, mesh=mesh)
    (want, aux), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params, tokens)
    opt = default_optimizer()
    alone, _ = make_train_step(cfg, opt, donate=False)(
        init_train_state(jax.random.PRNGKey(0), cfg, opt), {"tokens": tokens})
    return cfg, params, tokens, loss, want, aux, grads, alone


@pytest.mark.parametrize("ep", [2, 4], ids=["ep2", "ep4"])
def test_a_share_over_ep_equals_one_device(ep):
    """A JoyAI-LLM-Flash-shaped model (latent attention, a leading dense
    layer, sigmoid top-4 of 16 with a selection bias, a shared expert, a
    predicted-ahead module) of which this host holds 8 experts, on
    ``MeshConfig(ep=ep)`` with its tokens split over the ranks and the
    exchange between them: the loss, every gradient, the experts' counts
    and the selection bias a train step moves are the one-device
    program's."""
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    cfg, params, tokens, loss, want, (m1, c1), g1, alone = \
        _joyai_on_one_device()
    mesh = make_mesh(MeshConfig(ep=ep), devices=jax.devices()[:ep])
    rows = named_sharding(mesh, "batch", None)
    assert rows.spec == P(("dp", "fsdp", "ep"), None)
    with use_mesh(mesh):
        sharded = shard_pytree(params, param_logical_axes(cfg), mesh)
        assert sharded["layers"][1]["w_gate"].sharding.spec[1] == "ep"
        toks = jax.device_put(tokens, rows)
        (got, (m2, c2)), g2 = jax.jit(jax.value_and_grad(
            functools.partial(loss, mesh=mesh), has_aux=True))(sharded, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("loss", "mtp_loss", "moe_held_share",
                 "moe_load_max_over_mean"):
        assert float(m2[name]) == pytest.approx(float(m1[name]), rel=1e-5)
    assert float(m2["moe_dropped"]) == 0
    assert float(m1["moe_rank_rows_max_over_mean"]) == 1.0
    assert 1.0 <= float(m2["moe_rank_rows_max_over_mean"]) <= ep
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_array_equal(a, b)     # over ALL the token shards
    worst = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
        jax.device_get(g2), g1)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst
    # one train step through the normal path: the bias moves by the host's
    # counts, the same way on every rank
    opt = default_optimizer()
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh=mesh)
    spread, metrics = make_train_step(cfg, opt, mesh=mesh, donate=False)(
        state, {"tokens": toks})
    for a, b in ((alone.params["layers"][1], spread.params["layers"][1]),
                 (alone.params["mtp"]["layers"],
                  spread.params["mtp"]["layers"])):
        np.testing.assert_array_equal(a["router_bias"], b["router_bias"])
    assert np.isfinite(float(metrics["grad_norm"]))
