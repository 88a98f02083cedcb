"""Phi-4-mini-flash-reasoning's decoder at CPU size, float32 (the SambaY
rule: Mamba-1 layers, differential attention under a window, in full and
from later layers onto ONE layer's keys and values, gated memory units on
ONE scan's output) against the plain reference
``benchmark/reference/phi4flash.py`` — loss, each token's loss and every
gradient, the shared arrays' from all their readers; the selective scan's
forms against the recurrence; the differential combination against the
plain mixer; which layer is what; what a layer hands to later ones; a mesh;
the train step; the configuration file.  The tiny model is
``tests/tiny_models.py``'s row ``phi4flash``."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loops import train
from benchmark.reference import phi4flash
from ray_tpu.models import llama
from ray_tpu.models.blocks import MIXERS, attention as attention_block
from ray_tpu.models.blocks.base import Ctx
from ray_tpu.models.llama import (
    LlamaConfig, init_params, loss_fn, sambay_mixers)
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.layers import rms_norm
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

import tiny_models
from tiny_models import (
    against_the_reference, fault_ids, program, stands_apart,
    train_step_reports)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "phi-4-mini-flash-reasoning-1of8"
ROW = tiny_models.ROWS["phi4flash"]
TOKENS = ROW.tokens
tiny = functools.partial(tiny_models.tiny, "phi4flash")
HIGHEST = jax.default_matmul_precision("highest")
LETTERS = {"mamba1": "M", "diff_sliding": "W", "diff_full": "F", "gmu": "G",
           "diff_cross": "C"}


# -- (a) against the plain reference ------------------------------------------

@pytest.mark.parametrize("depth,impl", [
    (8, "reference"), (8, "flash-under-the-checkpoint"), (12, "reference")])
def test_loss_token_losses_and_gradients_equal_the_plain_reference(
        depth, impl):
    """Depth 8 is M W M W | M F | G C; depth 12 has TWO units on the memory
    of layer 6 and TWO cross layers on layer 7's keys and values, so that
    each shared array's gradient is a sum over its readers — every leaf of
    the producers' (``s6_*`` of the last Mamba layer, ``wk``, ``wv``, ``bv``
    of the full layer) against the reference's."""
    kw = {} if impl == "reference" else dict(attn_impl="flash", remat=True)
    extra = {} if depth == 8 else dict(
        num_layers=12, conf={"num_hidden_layers": 12})
    # a key's bias adds ONE number to every score of a query, which the
    # softmax takes out again: the loss does not depend on it
    _, got, _, grads = against_the_reference("phi4flash", dead=("bk",), **kw,
                                             **extra)
    assert "".join(LETTERS[m] for m, _ in program(
        "phi4flash", **kw, **{k: v for k, v in extra.items() if k != "conf"}
    ).cfg.layer_kinds) == {8: "MWMWMFGC", 12: "MWMWMWMFGCGC"}[depth]
    assert float(got["s6_state_absmax"]) > 0.0
    producers = [g for g in grads["layers"] if "s6_A_log" in g][-1], [
        g for g in grads["layers"] if "wk" in g][-1]
    for path, leaf in jax.tree_util.tree_leaves_with_path(producers):
        assert path[-1].key == "bk" or float(jnp.max(jnp.abs(leaf))) > 0.0


def test_the_reported_lambda_is_the_attention_layers_mean():
    side = program("phi4flash")
    _, parts = side.loss(side.params)
    want = []
    for i, (mixer, _) in enumerate(side.cfg.layer_kinds):
        if not mixer.startswith("diff_"):
            continue
        lp = jax.tree.map(lambda a: a[0], side.params["layers"][i])
        want.append(
            np.exp(np.sum(lp["lambda_q1"] * lp["lambda_k1"]))
            - np.exp(np.sum(lp["lambda_q2"] * lp["lambda_k2"]))
            + 0.8 - 0.6 * np.exp(-0.3 * i))
    assert len(want) == 4
    np.testing.assert_allclose(parts["diff_lambda"], np.mean(want),
                               rtol=1e-6)


@pytest.mark.parametrize("fault", fault_ids("phi4flash"))
def test_a_changed_part_stands_apart_from_the_reference(fault):
    """Each fault the chip check is held to (the configuration file's
    ``check.why``; the row's ``faults``), put into the PROGRAM at CPU size:
    the per-token losses part from the reference's by far more than
    rounding (a sound program's are within 3e-5)."""
    stands_apart("phi4flash", fault)


# -- (b) the selective scan ---------------------------------------------------

def _scan_operands(batch, s, channels, n, dt_shift, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, s, channels))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, channels))
                         + dt_shift)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                          (channels, n)) * jax.random.uniform(
                              ks[5], (channels, 1), minval=0.5, maxval=1.5)
    return (x, dt, a, jax.random.normal(ks[2], (batch, s, n)),
            jax.random.normal(ks[3], (batch, s, n)),
            jax.random.normal(ks[4], (channels,)))


def _hold_to_the_recurrence(form, args, rtol=2e-5):
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(fn):
        return lambda *t: jnp.sum(fn(*t)[0] * weights)

    want, _ = jax.jit(ssm.selscan_reference)(*args)
    got, peak = jax.jit(form)(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= rtol * scale
    assert np.isfinite(float(peak)) and float(peak) > 0.0
    every = tuple(range(6))
    want_g = jax.jit(jax.grad(loss(ssm.selscan_reference), every))(*args)
    got_g = jax.jit(jax.grad(loss(form), every))(*args)
    for name, g, w in zip("x dt a b c d".split(), got_g, want_g):
        assert np.isfinite(np.asarray(g)).all(), name
        assert float(jnp.max(jnp.abs(g - w))) <= rtol * (
            float(jnp.max(jnp.abs(w))) + 1e-12), name


# dt near 0 (softplus(-12): 6e-6), as drawn at initialisation, and near its
# largest (softplus(+6) x A of -16 x 1.5: a decay of exp(-150) a token)
@pytest.mark.parametrize("dt_shift", [-12.0, -3.0, 6.0])
@pytest.mark.parametrize("s,chunk", [(37, 16), (64, 64), (130, 64)])
def test_the_xla_form_is_the_recurrence(s, chunk, dt_shift):
    args = _scan_operands(2, s, 48, 16, dt_shift)
    _hold_to_the_recurrence(
        lambda *t: ssm.selscan_xla(*t, chunk=chunk), args)


@pytest.mark.parametrize("s,n,dt_shift", [
    (130, 4, -3.0), (200, 16, 6.0), (96, 16, -12.0)])
def test_the_kernels_are_the_recurrence(s, n, dt_shift):
    """``selscan_fwd`` / ``selscan_bwd`` (interpreted) over TWO channel
    blocks and a sequence that is no multiple of their chunk."""
    args = _scan_operands(1, s, 2048, n, dt_shift)
    assert ssm.selscan_kernels_fit(2048) and not ssm.selscan_kernels_fit(1536)
    _hold_to_the_recurrence(ssm.selscan_kernels, args)


def test_the_state_the_forms_report_is_a_chunks_end():
    args = _scan_operands(1, 256, 1024, 4, -3.0)
    _, every_token = jax.jit(ssm.selscan_reference)(*args)
    _, xla = jax.jit(lambda *t: ssm.selscan_xla(*t, chunk=64))(*args)
    _, kernels = jax.jit(ssm.selscan_kernels)(*args)
    assert 0.0 < float(kernels) <= float(xla) <= float(every_token)


# -- (c) the differential combination -----------------------------------------

def _one_layer(mixer, cfg, seed=3):
    """A layer's tensors (unstacked, norms drawn) of ``mixer`` and an input."""
    shapes = MIXERS[mixer].shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 1)
    lp = {name: p.init(k, p.shape) * (
        jax.random.uniform(k, p.shape, minval=0.5, maxval=1.5)
        if name.endswith("norm") else 1.0)
        for k, (name, p) in zip(keys, shapes.items())}
    x = jax.random.normal(keys[-1], (2, 64, cfg.embed_dim))
    return dict(lp, layer_index=jnp.float32(3.0)), x


def _mixer(name, cfg):
    ctx = Ctx(cfg, None, lambda x, ax: x, False)
    return functools.partial(MIXERS[name].apply, ctx)


def test_with_lambda_zero_it_is_the_plain_mixer_on_the_doubled_values():
    """``lambda = 0`` (``exp(lq2 . lk2) = 1 + lambda_init``): a pair's
    output is its FIRST head's plain softmax attention over the pair's first
    key head and ``[v1 | v2]``, normed and scaled."""
    cfg = tiny()
    lp, x = _one_layer("diff_full", cfg)
    start = 0.8 - 0.6 * np.exp(-0.3 * 3.0)
    dh = cfg.head_dim
    lp.update(lambda_q1=jnp.zeros(dh), lambda_k1=jnp.zeros(dh),
              lambda_q2=jnp.full(dh, 1.0),
              lambda_k2=jnp.full(dh, np.log(2.0 + start) / dh))
    with HIGHEST:
        # exp(0) - exp(log(2 + start)) + start = -1: a1 + a2; now halve it
        lp["lambda_k2"] = jnp.full(dh, np.log(1.0 + start) / dh)
        y, aux, made = _mixer("diff_full", cfg)(
            x, {"diff_lambda": jnp.float32(0)}, lp, residual=False)
        assert abs(float(aux["diff_lambda"])) < 1e-6
        h = attention_block.block_in(x, lp["attn_norm"], cfg,
                                     lp["attn_norm_bias"])
        b, s = x.shape[:2]
        q = (h @ lp["wq"] + lp["bq"]).reshape(b, s, 2, 2, dh)[:, :, :, 0]
        k = (h @ lp["wk"] + lp["bk"]).reshape(b, s, 1, 2, dh)[:, :, :, 0]
        v = (h @ lp["wv"] + lp["bv"]).reshape(b, s, 1, 2 * dh)
        plain = mha_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
        o = rms_norm(plain, lp["diff_norm"], cfg.norm_eps) * (1.0 - start)
        want = o.reshape(b, s, -1) @ lp["wo"] + lp["bo"]
    np.testing.assert_allclose(y, want, atol=2e-6)
    np.testing.assert_allclose(made["diff_values"], v, atol=1e-6)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_a_window_of_every_key_is_the_full_layer(impl):
    cfg = tiny(sliding_window=64, attn_impl=impl)
    lp, x = _one_layer("diff_sliding", cfg)
    zero = {k: jnp.float32(0) for k in MIXERS["diff_sliding"].stats(cfg)}
    with HIGHEST:
        windowed, _ = _mixer("diff_sliding", cfg)(x, zero, lp)
        full, _, _ = _mixer("diff_full", cfg)(x, zero, lp)
        cut, _ = _mixer("diff_sliding", tiny(attn_impl=impl))(x, zero, lp)
    np.testing.assert_array_equal(windowed, full)
    assert float(jnp.max(jnp.abs(cut - full))) > 1e-3


def test_the_flash_kernels_take_the_halves_in_one_grouped_call():
    """The kernels (interpreted) at two pairs on one: the layer is the XLA
    form's to rounding, windowed and in full."""
    for mixer in ("diff_sliding", "diff_full"):
        lp, x = _one_layer(mixer, tiny())
        zero = {k: jnp.float32(0) for k in MIXERS[mixer].stats(tiny())}
        with HIGHEST:
            want = _mixer(mixer, tiny())(x, zero, lp)[0]
            got = _mixer(mixer, tiny(attn_impl="flash"))(x, zero, lp)[0]
        np.testing.assert_allclose(got, want, atol=1e-5)


# -- (d) which layer is what, and what a layer hands on -----------------------

def test_the_published_depth_is_m_w_eight_times_m_f_then_g_c_seven_times():
    assert "".join(LETTERS[m] for m in sambay_mixers(32)) == (
        "MW" * 8 + "MF" + "GC" * 7)
    assert phi4flash.kinds(32) == "MW" * 8 + "MF" + "GC" * 7
    for depth in (4, 8, 12, 16, 32):
        assert "".join(LETTERS[m] for m in sambay_mixers(depth)
                       ) == phi4flash.kinds(depth)
    assert "G" not in phi4flash.kinds(4) and "C" not in phi4flash.kinds(4)
    with pytest.raises(NotImplementedError, match="multiple of 4"):
        tiny(num_layers=6)


def test_a_run_hands_on_what_a_later_run_reads_and_nothing_else():
    """Depth 8: the last Mamba layer alone publishes its memory (the two
    before it are followed by another publisher), the full layer its keys
    and values, the windowed layers nothing; a reader before any publisher
    is refused."""
    runs = tiny().layer_runs
    assert llama._published(runs) == (
        (), (), (), (), ("s6_memory",), ("diff_keys", "diff_values"), (),
        ())
    # nobody reads: nobody publishes
    assert llama._published(runs[:6]) == ((),) * 6
    with pytest.raises(ValueError, match="no earlier layer publishes"):
        llama._published((("gmu", 1), ("mamba1", 1)))
    with pytest.raises(ValueError, match="no earlier layer publishes"):
        LlamaConfig.tiny(layer_types=("diff_cross",) * 2, sliding_window=8)


def test_a_run_of_readers_holds_the_shared_arrays_once(monkeypatch):
    """Three units in ONE run: the memory is a constant of that run's scan
    (an operand that is not stacked over the layers), and a run of three
    producers hands out its LAST layer's alone."""
    monkeypatch.setattr(llama, "sambay_mixers", lambda depth: (
        "mamba1",) * 3 + ("gmu",) * 3)
    cfg = tiny(num_layers=8)
    assert cfg.layer_runs == (("mamba1", 3), ("gmu", 3))
    params = init_params(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg))(
        params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [2, 1, 3]
    memory = (2, 64, cfg.s6_inner)
    # the producers' first scan hands nothing out; the last layer's the
    # memory, once; the readers take it as a constant, never as a carry
    assert not [v for v in scans[0].outvars if v.aval.shape[1:] == memory]
    assert [v.aval.shape for v in scans[1].outvars
            if v.aval.shape[1:] == memory] == [(1, *memory)]
    readers = scans[2]
    consts = readers.invars[:readers.params["num_consts"]]
    carried = readers.invars[readers.params["num_consts"]:][
        :readers.params["num_carry"]]
    assert [v.aval.shape for v in consts].count(memory) == 1
    assert memory not in [v.aval.shape for v in carried]
    # and the three readers' gradients reach the one producer
    grads = jax.grad(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg)[0])(params)
    assert float(jnp.max(jnp.abs(grads["layers"][0]["s6_A_log"][2]))) > 0.0


def test_a_model_without_such_layers_is_scanned_as_before():
    """No publisher, no reader, no index: a scan's operands are the run's
    stack and nothing else."""
    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(lambda p: loss_fn(
        p, {"tokens": tiny_models.ROWS["dense"].tokens}, cfg))(params)
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    # (the constants are the rotary tables; the carry the stream and aux)
    stacked = scan.invars[scan.params["num_consts"]
                          + scan.params["num_carry"]:]
    assert len(stacked) == len(params["layers"])
    assert len(scan.outvars) == scan.params["num_carry"]
    assert llama._published(cfg.layer_runs) == ((),)


# -- (e) a mesh, the train step, the configuration file -----------------------

@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_on_a_mesh_the_model_is_one_devices(impl):
    """fsdp=2 x tp=2: the convolution and the scan per shard of the batch,
    the attention's halves over ``tp`` (a rank holds the pairs' first heads
    or their second, with their own key heads and the values once), the
    shared arrays handed from scan to scan under the partitioner."""
    cfg = tiny(attn_impl=impl)
    params = program("phi4flash").params
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    with HIGHEST:
        (want, want_m), want_g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": TOKENS}, cfg), has_aux=True))(
                params)
        (got, got_m), got_g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": TOKENS}, cfg, mesh=mesh),
            has_aux=True))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for name in ("s6_state_absmax", "diff_lambda"):
        np.testing.assert_allclose(got_m[name], want_m[name], rtol=1e-5)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            tiny_models.apart(got_g, want_g)):
        assert path[-1].key == "bk" or leaf < 1e-4, path


def test_the_train_step_opens_the_scopes_and_learns():
    train_step_reports("phi4flash")


def test_the_files_fields_reach_the_program_and_its_traffic_stays_in_the_slice():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        conf = json.load(f)
    cfg = train.program_config(conf)
    assert (cfg.vocab_size, cfg.num_layers, cfg.embed_dim, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.mlp_dim, cfg.sliding_window,
            cfg.mb_per_layer, cfg.norm_eps, cfg.tie_embeddings) == (
                25008, 8, 2560, 40, 20, 64, 10240, 512, 2, 1e-5, True)
    assert (cfg.s6_inner, cfg.s6_state, cfg.s6_conv, cfg.s6_rank,
            cfg.norm_type, cfg.attn_bias, cfg.position_embedding,
            cfg.num_experts) == (5120, 16, 4, 160, "layernorm", True,
                                 "nope", 0)
    assert "".join(LETTERS[m] for m, _ in cfg.layer_kinds) == "MWMWMFGC"
    kw = phi4flash.layer_kwargs(conf)
    assert (kw["depth"], kw["heads"], kw["kv_heads"], kw["window"],
            kw["state"], kw["eps"]) == (8, 40, 20, 512, 16, 1e-5)
    drawn = train.draw_tokens(np.random.default_rng([2**31 + 5, 0]), cfg, 1,
                              16384)
    assert drawn.shape == (1, 16385) and drawn.dtype == np.int32
    assert 0 <= drawn.min() and 24000 < drawn.max() < 25008
