"""A looped model (Ouro) at CPU size, float32: ONE stack of layers run T
times over the same weights (``models/llama.py``: ``LlamaConfig.looped``,
``_looped``, ``_ut_pass``), the exit gate and the head read after every
pass (``_exit_reading``) and the expected-loss objective (``_exit_mixture``)
against the plain reference ``benchmark/reference/ouro_looped.py`` — the
total, each pass's loss, the entropy, the last pass's logits, every
gradient —, the sharing by a property that needs no oracle, the exit
distribution, the program's size whatever T is, the refusals, the counts.
The tiny model is ``tests/tiny_models.py``'s row ``ouro``: two layers, four
passes, 64 positions."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, flops_ouro
from benchmark.reference import ouro_looped
from ray_tpu.models import llama
from ray_tpu.models.llama import forward, loss_fn
from ray_tpu.train.core import (
    STEP_SCOPES, default_optimizer, init_train_state, make_train_step)
from ray_tpu.util.tracing import scope_and_phase

import tiny_models
from tiny_models import against_the_reference, looped, program

ROW = tiny_models.ROWS["ouro"]
TOKENS = ROW.tokens
INPUTS, TARGETS = TOKENS[:, :-1], TOKENS[:, 1:]
tiny = functools.partial(tiny_models.tiny, "ouro")
HIGHEST = jax.default_matmul_precision("highest")
PARTS = ("loss", "ut_exit_entropy", "ut_expected_steps", "ut_steps")


# -- (a) against the plain reference ------------------------------------------

@pytest.mark.parametrize("passes,kw", [
    (4, {}), (4, dict(attn_impl="flash", remat=True)), (3, {}),
], ids=["T4", "T4-flash-under-the-checkpoints", "T3"])
def test_loss_parts_logits_and_gradients_equal_the_plain_reference(passes,
                                                                   kw):
    """The objective, each pass's mean loss, the entropy and the expected
    steps, the LAST pass's per-token losses and logits, and every gradient
    leaf — the gate's, its bias', the head's and the layers' by name —, on
    weights whose gate stands away from the uniform exit; also under the
    layer checkpoint and the head's own (``remat``)."""
    conf = None
    if passes != 4:
        kw["looped"], conf = looped(passes)
    _, got, want, grads = against_the_reference(
        "ouro", parts=PARTS + tuple(f"ut_nll_{t + 1}" for t in range(passes)),
        conf=conf, **kw)
    assert float(got["ut_steps"]) == passes
    assert f"ut_nll_{passes + 1}" not in got and "perplexity" not in got
    # the passes differ and the gate is away from uniform: a fault in the
    # weighting of the passes moves the total
    nll = [float(got[f"ut_nll_{t + 1}"]) for t in range(passes)]
    assert max(nll) - min(nll) > 1e-3
    assert float(got["ut_exit_entropy"]) < 0.97 * np.log(passes)
    for name in ("exit_gate", "exit_gate_bias", "lm_head", "final_norm"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 1e-4, name
    for name in ("wq", "w_down", "attn_post_norm", "mlp_post_norm"):
        assert float(jnp.max(jnp.abs(grads["layers"][name]))) > 1e-4, name
    side = program("ouro", **kw)
    with HIGHEST:
        logits, _ = jax.jit(lambda p: forward(p, INPUTS, side.cfg))(
            side.params)
        theirs = ouro_looped.logits(side.params, INPUTS,
                                    {**ROW.conf, **(conf or {})})
    assert logits.shape == (*INPUTS.shape, side.cfg.vocab_size)
    np.testing.assert_allclose(logits, theirs, atol=5e-5)


# -- (b) the sharing, by a property that needs no oracle -----------------------

def _unrolled(cfg, params, stacks):
    """The looped objective with pass ``t`` run on ``stacks[t]``: a Python
    loop over the program's own pass, reading and mixture.  Returns ``(loss,
    each pass's aux as the scan would have carried it from zero)``."""
    cst = llama._make_cst(None, None)
    x, read, auxes = llama._embed(params, INPUTS, cfg, None, None), [], []
    for t in range(cfg.passes):
        x, aux = llama._ut_pass(
            params, jax.tree.map(lambda a: a[t], stacks), x,
            llama._zero_aux(cfg), cfg, None, None)
        read.append(llama._exit_reading(params, x, TARGETS, cfg, cst))
        auxes.append(llama._mean_aux(aux, cfg, cfg.kind_runs))
    gates, nll = (jnp.stack(a) for a in zip(*read))
    return llama._exit_mixture(gates, nll, cfg)[0], auxes


def test_a_shared_tensors_gradient_is_the_sum_over_untied_copies(passes=3):
    """T untied copies of the stack, all equal to the shared one: the
    gradients of the copies add up to the shared stack's, tensor by
    tensor, and the loss is the same (the program of case (a)'s ``T3``)."""
    side = program("ouro", looped=looped(passes)[0])
    cfg, params = side.cfg, side.params
    copies = jax.tree.map(lambda a: jnp.stack([a] * passes), params["layers"])
    with HIGHEST:
        (total, _), shared = side.value_and_grad(params)
        loss, untied = jax.jit(jax.value_and_grad(
            lambda s: _unrolled(cfg, params, s)[0]))(copies)
    np.testing.assert_allclose(loss, total, rtol=1e-6)
    for name, grad in untied.items():
        assert float(jnp.max(jnp.abs(grad[0] - grad[-1]))) > 1e-6, name
        np.testing.assert_allclose(
            jnp.sum(grad, axis=0), shared["layers"][name], rtol=2e-4,
            atol=2e-6 * float(jnp.max(jnp.abs(shared["layers"][name]))),
            err_msg=name)


def test_a_mean_over_layers_is_a_mean_over_the_applications():
    """An expert layer's load-balancing loss, a mean over layers: of a
    looped model the mean over the T x N applications (each pass's own mean,
    averaged), and it weighs into the total at its coefficient."""
    cfg = tiny(looped=looped(2)[0], num_experts=4, num_selected=2,
               aux_loss_coef=0.01, z_loss_coef=0.001)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with HIGHEST:
        total, parts = jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg))(
            params)
        stacks = jax.tree.map(lambda a: jnp.stack([a] * 2), params["layers"])
        loss, auxes = jax.jit(lambda s: _unrolled(cfg, params, s))(stacks)
    for name in ("aux_loss", "z_loss"):
        assert abs(float(auxes[0][name]) - float(auxes[1][name])) > 1e-7
        np.testing.assert_allclose(
            parts[name], (auxes[0][name] + auxes[1][name]) / 2, rtol=1e-5)
    np.testing.assert_allclose(
        total, loss + 0.01 * parts["aux_loss"] + 0.001 * parts["z_loss"],
        rtol=1e-6)


# -- (c) one pass is the plain decoder -----------------------------------------

def test_one_pass_is_the_plain_decoder_and_no_group_traces_nothing_of_it():
    """``passes`` 1: ``p_1 = 1`` whatever the gate says, no entropy: the
    plain decoder's loss, logits and gradients on the same weights, the
    gate's own gradient nothing.  Without the group the program holds no
    gate, no scope of it and the metrics it always had."""
    one = program("ouro", looped=looped(1)[0])
    plain = program("ouro", looped=None)
    assert "exit_gate" not in plain.params
    weights = {k: v for k, v in one.params.items()
               if not k.startswith("exit_gate")}
    (total, parts), grads = one.value_and_grad(one.params)
    (want, want_parts), want_grads = plain.value_and_grad(weights)
    np.testing.assert_allclose(total, want, rtol=1e-6)
    np.testing.assert_allclose(parts["ut_nll_1"], want_parts["loss"],
                               rtol=1e-6)
    assert float(parts["ut_exit_entropy"]) == 0.0
    assert float(parts["ut_expected_steps"]) == 1.0
    np.testing.assert_array_equal(one.token_nll(one.params),
                                  plain.token_nll(weights))
    assert float(jnp.max(jnp.abs(grads.pop("exit_gate")))) == 0.0
    assert float(grads.pop("exit_gate_bias")) == 0.0
    for path, worst in jax.tree_util.tree_leaves_with_path(
            tiny_models.apart(grads, want_grads)):
        assert worst < 1e-5, path
    assert set(want_parts) == {"loss", "aux_loss", "perplexity"}
    text = jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, plain.cfg)[0]
                   ).lower(weights).as_text(debug_info=True)
    assert "ut_exit" not in text


# -- (d) the exit distribution and the mixture ---------------------------------

def test_the_exits_sum_to_one_and_the_last_takes_the_remainder():
    gates = 2.0 * jax.random.normal(jax.random.PRNGKey(3), (4, 2, 5))
    p = ouro_looped.exit_distribution(gates)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5)
    # a gate that always exits at once; one that never does
    first = ouro_looped.exit_distribution(jnp.full((4, 1), 40.0))
    np.testing.assert_allclose(first[:, 0], [1, 0, 0, 0], atol=1e-6)
    assert float(ouro_looped.entropy(first)[0]) == 0.0
    last = ouro_looped.exit_distribution(jnp.full((4, 1), -40.0))
    np.testing.assert_allclose(last[:, 0], [0, 0, 0, 1], atol=1e-6)


def test_the_mixtures_derivative_in_each_gate_logit_is_the_finite_difference():
    """The program's mixture in float64 on a handful of tokens: the loss is
    the equations', its derivative in every gate logit the central
    difference's, and the LAST pass's logit moves nothing."""
    cfg = tiny(looped=looped(4, 0.3)[0])
    with jax.enable_x64(True):
        keys = jax.random.split(jax.random.PRNGKey(5), 2)
        gates = 1.5 * jax.random.normal(keys[0], (4, 1, 3), jnp.float64)
        nll = 4.0 + jax.random.normal(keys[1], (4, 1, 3), jnp.float64)
        mix = jax.jit(lambda g: llama._exit_mixture(g, nll, cfg)[0])
        p = ouro_looped.exit_distribution(gates)
        np.testing.assert_allclose(mix(gates), jnp.mean(
            jnp.sum(p * nll, 0) - 0.3 * ouro_looped.entropy(p)), rtol=1e-12)
        grad = jax.grad(mix)(gates)
        assert float(jnp.max(jnp.abs(grad[3]))) == 0.0
        assert float(jnp.min(jnp.abs(grad[:3]))) > 1e-4
        eps = 1e-5
        for index in np.ndindex(*gates.shape):
            bump = jnp.zeros_like(gates).at[index].set(eps)
            np.testing.assert_allclose(
                grad[index], (mix(gates + bump) - mix(gates - bump))
                / (2 * eps), rtol=1e-6, atol=1e-10)
        _, stats = llama._exit_mixture(gates, nll, cfg)
        np.testing.assert_allclose(stats["ut_expected_steps"], jnp.mean(
            jnp.sum(jnp.arange(1, 5.0)[:, None, None] * p, 0)), rtol=1e-12)


# -- (e) the program does not grow with the passes -----------------------------

def _lowered_step(passes):
    cfg = tiny(looped=looped(passes)[0], remat=True)
    opt = default_optimizer()
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, opt),
                           jax.random.PRNGKey(0))
    return make_train_step(cfg, opt).lower(
        state, {"tokens": TOKENS}).as_text(debug_info=True)


def test_the_lowered_step_holds_one_layer_body_and_one_head_whatever_t_is():
    """T = 2 and T = 4 lower to the same products, loops and functions: one
    call site of the layer's products and of the head's (forward, made
    again, two of the backward pass); the objective's ops lie under
    ``ut_exit`` and no other step scope."""
    import re

    two, four = _lowered_step(2), _lowered_step(4)
    vocab = f"x{ROW.fields['vocab_size']}xf32"

    def products(text):
        return [line for line in text.splitlines()
                if "stablehlo.dot_general" in line]

    assert len(products(two)) == len(products(four))
    assert sum(vocab in line for line in products(four)) == 4
    assert two.count("stablehlo.while") == four.count("stablehlo.while")
    assert two.count("func.func") == four.count("func.func")
    # ... and what does follow T is the mixture's handful of lines a pass
    assert 0 < len(four.splitlines()) - len(two.splitlines()) < 100
    names = set(re.findall(r'loc\("([^"]*)"', four))
    exits = {n for n in names if "ut_exit" in n}
    assert exits and "ut_exit" in STEP_SCOPES
    assert {scope_and_phase(n, STEP_SCOPES)[0] for n in exits} == {"ut_exit"}
    assert {scope_and_phase(n, STEP_SCOPES)[1] for n in exits} == {
        "forward", "backward"}
    # the head's products made again for the backward pass are a remat
    assert ("lm_head", "remat") in {scope_and_phase(n, STEP_SCOPES)
                                    for n in names}


# -- (f) what is not built refuses by message ----------------------------------

@pytest.mark.parametrize("kw,said", [
    (dict(num_nextn=1), "predicted-ahead module"),
    (dict(hc_mult=4), "several residual streams"),
    (dict(block_diffusion=tiny_models.SDAR_NOISE), "block_diffusion"),
    (dict(attn_impl="ring"), "split over 'sp'"),
    (dict(num_experts=4, topk_method="noaux_tc", router_scoring="sigmoid"),
     "selection bias"),
    (dict(mb_per_layer=2, num_layers=8, num_kv_heads=2, sliding_window=8,
          block_norm="input"), "publishes or reads"),
    (dict(looped={"passes": 0, "entropy_coef": 0.05}), "passes >= 1"),
    (dict(looped={"passes": 4}), "entropy_coef"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else None)
def test_what_is_not_built_of_a_looped_model_is_refused(kw, said):
    with pytest.raises((NotImplementedError, ValueError), match=said):
        tiny(**kw)


def test_the_pipelined_paths_refuse_a_looped_model():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="ONCE a micro-batch"):
        llama.make_pipeline_stage_fn(cfg)
    with pytest.raises(NotImplementedError, match="ONCE a micro-batch"):
        llama.forward_pipelined({}, INPUTS, cfg, mesh=None,
                                num_microbatches=2)
    with pytest.raises(NotImplementedError, match="replaced forward pass"):
        loss_fn({}, {"tokens": TOKENS}, cfg, forward_fn=lambda p, t: None)
    assert llama.param_logical_axes(cfg)["exit_gate"] == ("embed",)
    assert llama.param_logical_axes(cfg)["exit_gate_bias"] == ()


# -- (g) the counts -------------------------------------------------------------

@pytest.mark.parametrize("passes", [1, 3, 4])
def test_the_flop_module_counts_every_use_and_every_parameter_once(passes):
    """At the tiny sizes, from the shapes ``init_params`` makes: a matrix of
    the stack is used T times a token, the head T times, the gate T - 1;
    attention's needed pairs T x N times; the parameters once."""
    conf = dict(ROW.conf, hidden_size=64, intermediate_size=32, head_dim=16,
                vocab_size=128, total_ut_steps=passes)
    cfg = tiny(looped=looped(passes)[0])
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert flops_ouro.total_params(conf) == sum(
        a.size for a in jax.tree.leaves(shapes))
    matrices = sum(a.size for name, a in shapes["layers"].items()
                   if not name.endswith("norm"))
    assert matrices == 2 * flops_ouro.layer_matmul_params(conf)
    seq = INPUTS.shape[1]
    pairs = 2 * passes * 6 * seq * 4 * 16     # flops.py's count a layer
    assert flops_ouro.train_flops_per_token(conf, seq) == 6 * (
        passes * matrices + passes * shapes["lm_head"].size
        + (passes - 1) * shapes["exit_gate"].size) + pairs
    assert flops_ouro.flash_step_flops(conf, 2, seq) == pairs * 2 * seq
    assert flops_ouro.flash_step_bytes(conf, 2, seq) == passes * \
        flops.flash_step_bytes(conf, 2, seq)
    assert flops_ouro.head_step_flops(conf, 2 * seq) == \
        6 * passes * 2 * seq * 64 * 128
