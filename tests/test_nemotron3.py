"""What Nemotron-3 adds to a pattern-string model, at CPU size: routed
experts that work in a LATENT a quarter of the hidden size (projected down
before the dispatch, up after the combine), more choices a token than
experts held, and a predicted-ahead module that is itself a pattern (``*``
then ``E``) — the program (``ray_tpu/models/llama.py``, ``blocks/ffn.py``,
``ops/moe.py``) against the plain reference
(``benchmark/reference/nemotron3.py``) on seeded weights in float32."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import nemotron3
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.ops.moe import moe_block
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
import tiny_models
from tiny_models import (
    against_the_reference, fault_ids, program, shares_add_up, stands_apart)

tiny = functools.partial(tiny_models.tiny, "nemotron3")
HIGHEST = jax.default_matmul_precision("highest")
NO_MODULE = dict(num_nextn=0, mtp_pattern="")


# -- (a) the configuration ----------------------------------------------------

def test_a_pattern_model_takes_a_module_that_is_a_pattern_and_no_other():
    cfg = tiny()
    assert cfg.mtp_runs == ((("attention", "none"), 1), (("none", "moe"), 1))
    assert tiny(mtp_pattern="EE*").mtp_runs == (
        (("none", "moe"), 2), (("attention", "none"), 1))
    params = init_params(jax.random.PRNGKey(0), cfg)
    star, expert = params["mtp"]["layers"]   # two stacks: two more scans
    assert sorted(star) == ["attn_norm", "wk", "wo", "wq", "wv"]
    assert expert["w_up"].shape == (1, 4, 16, 32)      # in the latent
    assert expert["w_down"].shape == (1, 4, 32, 16)
    assert expert["w_latent_in"].shape == (1, 64, 16)
    assert expert["w_latent_out"].shape == (1, 16, 64)
    assert expert["router"].shape == (1, 64, 16)       # the model's width
    assert expert["shared_up"].shape == (1, 64, 48)    # the FULL width
    with pytest.raises(ValueError, match="spelled the same way, by "
                                         "mtp_pattern, and not without one"):
        tiny(mtp_pattern="")
    with pytest.raises(ValueError, match="not without one"):
        tiny(num_nextn=0)
    with pytest.raises(ValueError, match=r"mtp_pattern holds \['X'\]"):
        tiny(mtp_pattern="*X")
    with pytest.raises(ValueError, match="of a layer_pattern model"):
        LlamaConfig.tiny(num_nextn=1, mtp_pattern="*E")
    # without the fields a model is what it was: no new tensor
    plain = init_params(jax.random.PRNGKey(0), tiny(moe_latent=0,
                                                    **NO_MODULE))
    assert "mtp" not in plain
    assert "w_latent_in" not in plain["layers"][1]
    assert plain["layers"][1]["w_up"].shape == (1, 4, 64, 32)


def test_the_residual_scale_goes_on_the_latents_up_projection_alone():
    """``rescale_prenorm_residual``: of the two matrices between an
    expert's hidden width and the residual, the one that WRITES to the
    residual takes the factor; in a model without a latent that is the
    experts' down matrix, as it was."""
    draw = lambda **kw: init_params(jax.random.PRNGKey(2), tiny(**kw))
    plain = draw()["layers"][1]
    scaled = draw(rescale_prenorm_residual=True, published_layers=8)[
        "layers"][1]
    factor = {"w_latent_out": 0.25, "shared_down": 0.25}
    for name, w in plain.items():
        np.testing.assert_allclose(scaled[name], w * factor.get(name, 1.0),
                                   rtol=1e-6, atol=0, err_msg=name)
    wide = draw(moe_latent=0)["layers"][1]
    wide_scaled = draw(moe_latent=0, rescale_prenorm_residual=True,
                       published_layers=8)["layers"][1]
    np.testing.assert_allclose(wide_scaled["w_down"], wide["w_down"] * 0.25,
                               rtol=1e-6)


# -- (b) the whole model against the reference --------------------------------

@pytest.mark.parametrize("latent", [16, 0], ids=["latent", "full_width"])
@pytest.mark.parametrize("module", [True, False], ids=["module", "stack"])
def test_loss_per_token_loss_and_gradients_equal_the_plain_reference(
        latent, module):
    kw = dict(moe_latent=latent, **({} if module else NO_MODULE))
    # the reference runs on THIS program's draw (its shapes follow ``kw``)
    conf = {"moe_latent_size": latent,
            "num_nextn_predict_layers": int(module)}
    parts = ("loss", "moe_held_share") + ("mtp_loss",) * module
    _, got, want, ours = against_the_reference(
        "nemotron3", parts=parts, grad_rtol=2e-4, conf=conf, **kw)
    assert float(got["moe_dropped"]) == 0.0
    assert 0.1 < float(got["moe_held_share"]) < 0.4    # a quarter is held
    assert ("mtp_loss" in want) == module
    stacks = ours["layers"] + (ours["mtp"]["layers"] if module else ())
    assert len(stacks) == 5 + 2 * module
    # every tensor has a gradient but the selection bias, which none reaches
    for stack in stacks:
        assert ("w_latent_in" in stack) == bool(latent and "router" in stack)
        for name, g in stack.items():
            assert bool(jnp.any(g != 0)) == (name != "router_bias"), name


def test_the_kernels_under_the_checkpoint_give_the_same_loss_and_gradients():
    """As a chip runs it — the flash kernel and the grouped kernels
    interpreted (their rows ``l`` wide), the layer checkpoint on, both in
    the stack's scans and in the module's two."""
    params = program("nemotron3").params
    (want, _), want_g = program("nemotron3").value_and_grad(params)
    (got, _), got_g = program("nemotron3", attn_impl="flash",
                              remat=True).value_and_grad(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    apart = tiny_models.apart(got_g, want_g)
    assert max(jax.tree.leaves(apart)) < 1e-4, apart


@pytest.mark.parametrize("fault", fault_ids("nemotron3"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """The six faults the chip check is shown to catch (PERF.md section 6;
    the row's ``faults``), at CPU size and in float32: with the part
    changed each token's loss — or, where the fault is in the module and
    the stack is sound, the module's loss — stands apart from the
    reference's by a hundred times what the sound program's does (3e-5
    nats and 2e-5 relative at most, the test above)."""
    read = stands_apart("nemotron3", fault)
    if fault == "the_modules_e_before_its_star":   # the stack is sound
        assert tiny_models.token_rms(read.side, read.params,
                                     read.want) < 3e-5


# -- (c) the shares add up ----------------------------------------------------

def _latent_layer(tokens=96, d=64, latent=16, m=32, shared=48, experts=16,
                  seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    normal = jax.random.normal
    return dict(
        x=normal(keys[0], (tokens, d)),
        mlp_norm=1.0 + 0.3 * normal(keys[1], (d,)),
        router=normal(keys[2], (d, experts)) * d ** -0.5,
        router_bias=0.05 * normal(keys[3], (experts,)),
        w_up=normal(keys[4], (experts, latent, m)) * latent ** -0.5,
        w_down=normal(keys[5], (experts, m, latent)) * m ** -0.5,
        shared_up=normal(keys[6], (d, shared)) * d ** -0.5,
        shared_down=normal(keys[7], (shared, d)) * shared ** -0.5,
        w_latent_in=normal(keys[8], (d, latent)) * d ** -0.5,
        w_latent_out=normal(keys[9], (latent, d)) * latent ** -0.5)


def _routed(p, first, held, **axes):
    """The routed part alone of the chip that holds ``held`` experts from
    ``first`` on — ITS ``W_out`` of its own partial sum —, 6 choices a
    token; its step counters beside it."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], None,
        *(jax.lax.dynamic_slice_in_dim(p[w], first, held)
          for w in ("w_up", "w_down")),
        latent=(p["w_latent_in"], p["w_latent_out"]),
        num_selected=6, norm_topk_prob=True, topk_norm_eps=1e-20,
        scoring="sigmoid", select_bias=p["router_bias"], gate_scale=5.0,
        first_expert=first, residual=False, **axes)


_share = jax.jit(_routed, static_argnums=2)


def test_the_shares_add_up_to_the_uncut_layer():
    """Chips 0..3 with four experts each — fewer than the six choices a
    token —: their routed parts, each share's ``W_out`` of its own partial
    sum, and the shared expert ONCE, are the whole layer as the reference
    has it."""
    p = _latent_layer()
    h = nemotron3.rms_norm(p["x"], p["mlp_norm"], 1e-6)
    shared = nemotron3.relu2(h, p["shared_up"], p["shared_down"])
    with HIGHEST:
        whole, _ = nemotron3.latent_expert_ffn(h[None], p, k=6, factor=5.0,
                                               first=0)
    shares_add_up("nemotron3", p, _share, whole[0], k=6, shared=shared,
                  atol=5e-5)


# -- (d) over an ``ep`` axis the exchange carries the latent ------------------

def test_over_ep_the_exchange_carries_rows_as_wide_as_the_latent():
    """Four ranks with two experts each: the result is the unmeshed
    layer's, and what the exchange gathers and scatters — read off the
    lowered text — is 16 wide, the latent, never the model's 64."""
    mesh = make_mesh(MeshConfig(ep=4), devices=jax.devices()[:4])
    p = _latent_layer()
    weights = P("ep", None, None)
    keys = ("x", "mlp_norm", "router", "router_bias", "w_up", "w_down",
            "w_latent_in", "w_latent_out")

    def rank(*args):
        q = dict(zip(keys, args))
        return _routed(q, 0, q["w_up"].shape[0], expert_axis="ep")

    fn = jax.jit(jax.shard_map(
        rank, mesh=mesh, in_specs=(P("ep", None), P(), P(), P(), weights,
                                   weights, P(), P()),
        out_specs=(P("ep", None), P()), check_vma=False))
    args = [p[k][:8] if k in ("w_up", "w_down") else p[k] for k in keys]
    y, stats = fn(*args)
    want, alone = _share(p, 0, 8)
    np.testing.assert_allclose(y, want, atol=5e-5)
    np.testing.assert_array_equal(stats["counts"], alone["counts"])
    assert float(stats["dropped"]) == 0.0
    text = fn.lower(*args).as_text()
    moved = [line for line in text.splitlines()
             if re.search(r"stablehlo\.(all_gather|reduce_scatter)", line)]
    widths = {tuple(int(n) for n in shape.split("x")[:-1])
              for line in moved for shape in re.findall(
                  r"tensor<([\dx]+x)f32>", line)}
    # the tokens in (24 a rank, 96 a group) and the sums out, all 16 wide;
    # beside them each token's 6 gates
    assert widths == {(24, 16), (96, 16), (24, 6), (96, 6)}, (widths, moved)
