"""The layer checkpoint's policy (``models/llama.py::_checkpoint``): the
backward pass recomputes a layer except the residuals named in
``ops/attention.py`` and ``ops/moe.py``.  Saving a value instead of
recomputing it changes no arithmetic, so gradients equal a bare
``jax.checkpoint`` to the bit; what changes is what the gradient's program
holds: the ``flash_fwd`` kernel and the expert layer's sorts once a layer,
not twice."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import LlamaConfig, init_params, loss_fn, param_logical_axes
from ray_tpu.models import llama
from ray_tpu.models.llama import forward_pipelined
from ray_tpu.parallel import MeshConfig, make_mesh, shard_pytree, use_mesh

SEQ = 64
CONFIGS = {
    "dense_gqa": dict(num_kv_heads=2),
    "moe": dict(num_experts=8, num_selected=3, qk_norm=True,
                z_loss_coef=0.001),
}
MESHES = {"one_device": None, "mesh4": dict(fsdp=2, tp=2)}


def _cfg(model, **kw):
    return LlamaConfig.tiny(attn_impl="flash", remat=True, **CONFIGS[model],
                            **kw)


ROWS = 4


def _inputs(cfg, rows=ROWS):
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, SEQ + 1), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    return params, tokens


def _grad_fn(cfg, mesh_kw):
    """(gradient function, its arguments) on one device or a 4-device
    mesh, where flash and the expert layer run inside ``shard_map``."""
    params, tokens = _inputs(cfg)
    if mesh_kw is None:
        return jax.grad(
            lambda p, t: loss_fn(p, {"tokens": t}, cfg)[0]), (params, tokens)
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:4])
    with use_mesh(mesh):
        params = shard_pytree(params, param_logical_axes(cfg), mesh)
        tokens = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    return jax.grad(
        lambda p, t: loss_fn(p, {"tokens": t}, cfg, mesh=mesh)[0]), (
            params, tokens)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _counts(grad, args, cfg):
    """What one layer of the gradient's program holds (the scan's body is
    traced once, so a count is per layer).  A row gather reads ``(T, d)``
    tokens into ``(T * k, d)`` sorted rows: ``_dispatch`` in the forward
    pass and again when it is rematerialised (cheaper than keeping the
    rows: ``ops/moe.py::SAVED_RESIDUALS``), and the cotangent's rows in
    ``_combine``'s gradient.  Two sorts build the row index
    (``_row_index``: the routing sort and its inverse) and a third puts
    the gates' gradient in place in the backward pass."""
    eqns = list(_eqns(jax.make_jaxpr(grad)(*args).jaxpr))
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    return {"flash_fwd": kernels.count("flash_fwd"),
            "flash_dkv": kernels.count("flash_dkv"),
            "flash_dq": kernels.count("flash_dq"),
            "sorts": sum(e.primitive.name == "sort" for e in eqns),
            "row_gathers": sum(
                e.primitive.name == "gather"
                and e.outvars[0].aval.shape == (
                    e.invars[0].aval.shape[0] * cfg.num_selected,
                    cfg.embed_dim)
                for e in eqns) if cfg.num_experts else 0}


def _max_abs_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))),
        jax.device_get(a), jax.device_get(b))))


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("model", list(CONFIGS))
def test_gradients_equal_a_bare_checkpoint_and_no_checkpoint(
        model, mesh_name, monkeypatch):
    cfg = _cfg(model)
    grad, args = _grad_fn(cfg, MESHES[mesh_name])
    kept = jax.jit(grad)(*args)
    plain, _ = _grad_fn(dataclasses.replace(cfg, remat=False),
                        MESHES[mesh_name])
    assert _max_abs_diff(kept, jax.jit(plain)(*args)) < 1e-6
    monkeypatch.setattr(llama, "_checkpoint", jax.checkpoint)
    bare, _ = _grad_fn(cfg, MESHES[mesh_name])
    assert _max_abs_diff(kept, jax.jit(bare)(*args)) == 0.0


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("model", list(CONFIGS))
def test_backward_runs_no_second_flash_fwd_and_no_second_dispatch(
        model, mesh_name, monkeypatch):
    cfg = _cfg(model)
    moe = bool(cfg.num_experts)
    grad, args = _grad_fn(cfg, MESHES[mesh_name])
    kept = _counts(grad, args, cfg)
    assert kept == {"flash_fwd": 1, "flash_dkv": 1, "flash_dq": 0,
                    "sorts": 3 * moe, "row_gathers": 3 * moe}
    # The same count sees the second copies under a bare checkpoint.
    monkeypatch.setattr(llama, "_checkpoint", jax.checkpoint)
    bare, _ = _grad_fn(cfg, MESHES[mesh_name])
    assert _counts(bare, args, cfg) == {
        "flash_fwd": 2, "flash_dkv": 1, "flash_dq": 0, "sorts": 5 * moe,
        "row_gathers": 3 * moe}


def _named(cfg):
    """name -> abstract value of every ``checkpoint_name`` in the
    gradient's program."""
    params, tokens = _inputs(cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0]))(params)
    return {e.params["name"]: e.outvars[0].aval for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "name"}


def test_saved_log_sum_exp_is_one_float_a_row():
    """The policy holds ``(b, h, s)`` float32, not the 128 lanes the
    kernel writes (twice the output's bytes)."""
    cfg = _cfg("dense_gqa")
    named = _named(cfg)
    b, h = ROWS, cfg.num_heads
    assert named["flash_lse"].shape == (b, h, SEQ)
    assert named["flash_lse"].dtype == jnp.float32
    assert named["flash_out"].shape == (b, h, SEQ, cfg.head_dim)


@pytest.mark.parametrize("model", list(CONFIGS))
def test_reference_attention_produces_no_flash_name_and_differentiates(model):
    cfg = dataclasses.replace(_cfg(model), attn_impl="reference")
    grad, args = _grad_fn(cfg, None)
    plain, _ = _grad_fn(dataclasses.replace(cfg, remat=False), None)
    assert _max_abs_diff(jax.jit(grad)(*args), jax.jit(plain)(*args)) < 1e-6
    assert _counts(grad, args, cfg)["flash_fwd"] == 0


@pytest.mark.parametrize("attn", ["reference", "ring"])
def test_pipelined_forward_differentiates_under_the_policy(attn):
    cfg = LlamaConfig.tiny(num_layers=4, attn_impl=attn, remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    want = jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    mesh = make_mesh(MeshConfig(dp=2, pp=2, sp=2 if attn == "ring" else 1,
                                tp=1 if attn == "ring" else 2))
    with use_mesh(mesh):
        sharded = shard_pytree(params, param_logical_axes(cfg), mesh)
        toks = jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        got = jax.jit(jax.grad(lambda p, t: loss_fn(
            p, {"tokens": t}, cfg, mesh=mesh,
            forward_fn=lambda p_, x: forward_pipelined(
                p_, x, cfg, mesh=mesh, num_microbatches=4))[0]))(
                    sharded, toks)
    assert _max_abs_diff(got, want) < 5e-4


def test_policy_names_are_the_ones_the_ops_produce():
    """One helper, fixed names: a name the policy lists and no op makes
    (or the reverse) would save nothing without failing."""
    from ray_tpu.ops import attention, moe
    assert set(_named(_cfg("moe"))) == set(
        attention.SAVED_RESIDUALS + moe.SAVED_RESIDUALS)
