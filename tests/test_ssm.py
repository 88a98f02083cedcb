"""The state-space mixer (``ops/ssm.py``) and the hybrid model it makes
possible (``models/llama.py``: a pattern of Mamba-2 and attention layers,
NoPE, a given softmax scale, three multipliers, a tied head), on the CPU at
tiny sizes.  The model's yardstick is the benchmark's own plain reference
(``benchmark/reference/granite_hybrid.py``: the recurrence a token at a
time, nothing shared with the code under test)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.blocks import mamba
from ray_tpu.models.llama import (
    LlamaConfig, forward_pipelined, init_params, loss_fn,
    make_pipeline_stage_fn, param_logical_axes, pipeline_stage_params)
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssm import (
    causal_conv1d, gated_rms_norm, kernels_fit, norm_kernels_fit,
    ssd_chunked, ssd_kernels, ssd_reference, ssd_xla)
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.core import init_train_state, train_state_shardings
import tiny_models
from tiny_models import ROWS, against_the_reference, program, reference

HIGHEST = jax.default_matmul_precision("highest")


def _scan_inputs(seq, seed=0, batch=2, heads=4, p=8, groups=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (batch, seq, heads))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    return (f(batch, seq, heads, p), dt, a, f(batch, seq, groups, n),
            f(batch, seq, groups, n), f(heads))


def _value_and_grads(form, args, weight):
    """``form(*args)`` and the gradients of its sum weighted by ``weight``
    (in float32) to all six arguments: ONE compiled program."""
    def scalar(*t):
        out = form(*t)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=range(6), has_aux=True))(*args)
    return out, grads


@pytest.mark.parametrize("form", ["chunked", "kernels"])
@pytest.mark.parametrize("seq,chunk", [(40, 8), (32, 32), (24, 256), (37, 8)],
                         ids=["several-chunks", "one-chunk",
                              "shorter-than-a-chunk", "ragged"])
def test_ssd_chunked_equals_the_recurrence(seq, chunk, form):
    """Values and every gradient, against the recurrence a token at a
    time: a sequence of several chunks, one that is a single chunk, and one
    that no chunk divides (padded with tokens that neither decay nor add).
    ``chunked`` is the public call, which takes the XLA form at these
    sizes (two groups); ``kernels`` the Pallas kernels, interpreted, on
    one group, held to the XLA form as well."""
    if form == "chunked":
        args, run = _scan_inputs(seq), ssd_chunked
    else:
        args, run = _scan_inputs(seq, groups=1), ssd_kernels
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=args[0].shape), jnp.float32)
    forms = (lambda *t: run(*t, chunk=chunk), ssd_reference,
             lambda *t: ssd_xla(*t, chunk=chunk))
    with HIGHEST:
        (got, want, xla), grads = zip(*(
            _value_and_grads(f, args, weight) for f in forms))
    assert got.shape == want.shape and got.dtype == args[0].dtype
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got, xla, atol=2e-5, rtol=2e-5)
    for name, g, w, x in zip("x dt a b c d".split(), *grads):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4, err_msg=name)
        np.testing.assert_allclose(g, x, atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("form,groups", [(ssd_xla, 2), (ssd_kernels, 1)],
                         ids=["xla", "kernels"])
def test_ssd_chunked_in_bfloat16_keeps_its_decays_in_float32(form, groups):
    """Operands of the big products in the input's dtype, decays and the
    carried state in float32: a bfloat16 call stays within bfloat16's
    rounding of the float32 recurrence over 8 chunks."""
    x, dt, a, b, c, d = _scan_inputs(64, seed=3, groups=groups)
    got = form(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
               c.astype(jnp.bfloat16), d, chunk=8)
    assert got.dtype == jnp.bfloat16
    want = ssd_reference(x, dt, a, b, c, d)
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.sqrt(jnp.mean(err ** 2))) < 0.02 * float(
        jnp.sqrt(jnp.mean(want ** 2)))


def _rms_apart(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def _records_the_kernels(monkeypatch):
    """``ssd_chunked``'s kernel form patched to note each call's shape;
    returns the list."""
    from ray_tpu.ops import ssm

    calls = []
    monkeypatch.setattr(ssm, "ssd_kernels", lambda *t, **kw: (
        calls.append(t[0].shape), ssd_kernels(*t, **kw))[1])
    return calls


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_several_groups_at_chunks_of_128_equal_the_recurrence(dtype, tol,
                                                              monkeypatch):
    """Nemotron-H's mixer in small (heads of 64, state 128, chunks of 128,
    SEVERAL groups: head ``i`` reads B and C of group ``i // (heads /
    groups)``) at 8 heads in 4 groups and 300 positions, which the chunk
    does not divide.  A pair of heads fills a lane block inside one group,
    so ``ssd_chunked`` takes the Pallas kernels (interpreted here), which
    cut B and C at the grid step's group, and values and the six
    gradients are the recurrence's, which repeats each group's B and C to
    its heads; one group's B and C for every head is another function."""
    assert kernels_fit(8, 64, 1, 128, 128)
    assert kernels_fit(8, 64, 4, 128, 128)
    calls = _records_the_kernels(monkeypatch)
    args = _scan_inputs(300, seed=6, batch=1, heads=8, p=64, groups=4, n=128)
    cast = tuple(t.astype(dtype) if i in (0, 3, 4) else t
                 for i, t in enumerate(args))
    weight = jnp.asarray(np.random.default_rng(3).normal(
        size=args[0].shape), jnp.float32)
    chunked = lambda *t: ssd_chunked(*t, chunk=128)  # noqa: E731
    with HIGHEST:
        want, want_grads = _value_and_grads(ssd_reference, args, weight)
        got, grads = _value_and_grads(chunked, cast, weight)
        x, dt, a, b, c, d = args
        first = lambda t: jnp.repeat(t[:, :, :1], 4, 2)  # noqa: E731
        wrong = jax.jit(ssd_reference)(x, dt, a, first(b), first(c), d)
    assert calls and all(shape == (1, 300, 8, 64) for shape in calls)
    assert got.shape == want.shape and got.dtype == dtype
    assert _rms_apart(got, want) < tol
    assert _rms_apart(wrong, want) > 0.5
    for name, g, w in zip("x dt a b c d".split(), grads, want_grads):
        assert g.shape == w.shape and _rms_apart(g, w) < tol, name


def _planned(batch, seq, heads, p, groups, n, q):
    """``_plan``'s grid, blocks a step and steps a group for a call of
    these shapes (and the blocks a trip of the step's loop)."""
    from ray_tpu.ops import ssm

    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)
    sp = ssm._plan(shape(batch, seq, heads * p), shape(batch, seq, heads),
                   shape(batch, seq, groups * n), q, p, groups, reverse=False)
    assert sp["blocking"]["nb"] % sp["blocking"]["trip"] == 0
    return (sp["grid"], sp["blocking"]["nb"], sp["group_steps"],
            sp["blocking"]["trip"])


@pytest.mark.parametrize("heads,p,groups,plan", [
    (4, 64, 2, ((2, 3, 2), 1, 1)),
    (8, 64, 2, ((2, 3, 2), 2, 1)),
    (12, 64, 2, ((2, 3, 6), 1, 3)),
    (6, 128, 2, ((2, 3, 6), 1, 3)),
], ids=["a-block-a-group", "a-step-a-group", "a-group-over-three-steps",
        "three-steps-of-one-head"])
def test_a_groups_heads_read_its_b_and_c_whatever_the_blocking(
        heads, p, groups, plan, monkeypatch):
    """The three layouts of a group on the kernels' grid, at 300 positions
    in chunks of 128 (which do not divide them), two rows, float32: a
    group that is ONE head block (a step is a block), a group that is one
    step of several blocks (Nemotron-H's: 4 blocks, one step), and a
    group spread over several steps, whose gradients to B and C
    accumulate from step to step and are written at the group's last —
    with a pair of heads a block and with a head that fills the lanes.
    Values and the six gradients are the recurrence's and the XLA form's,
    B's and C's the sums over each group's own heads."""
    assert _planned(2, 384, heads, p, groups, 128, 128)[:3] == plan
    calls = _records_the_kernels(monkeypatch)
    args = _scan_inputs(300, seed=8, heads=heads, p=p, groups=groups, n=128)
    weight = jnp.asarray(np.random.default_rng(4).normal(
        size=args[0].shape), jnp.float32)
    forms = (lambda *t: ssd_chunked(*t, chunk=128), ssd_reference,
             lambda *t: ssd_xla(*t, chunk=128))
    with HIGHEST:
        (got, want, xla), grads = zip(*(
            _value_and_grads(f, args, weight) for f in forms))
    assert calls and all(shape == (2, 300, heads, p) for shape in calls)
    assert _rms_apart(got, want) < 1e-4 and _rms_apart(got, xla) < 1e-4
    for name, g, w, x in zip("x dt a b c d".split(), *grads):
        assert g.shape == w.shape and np.all(np.isfinite(g)), name
        assert _rms_apart(g, w) < 1e-4 and _rms_apart(g, x) < 1e-4, name


@pytest.mark.parametrize("shapes,plan", [
    ((1, 8192, 64, 64, 1, 128, 256), ((1, 32, 4), 8, 4, 1)),
    ((2, 8192, 64, 64, 8, 128, 128), ((2, 64, 8), 4, 1, 4)),
], ids=["granite-4.0-h-micro", "nemotron-h"])
def test_the_published_shapes_blocking(shapes, plan):
    """The one-group call keeps the blocking it had (granite's 32 head
    blocks in its one group: 8 a step, 4 steps a chunk, the step a loop of
    one block a trip); Nemotron-H's 4 blocks a group are one step, a
    chunk its 8 groups in turn, and at chunks of 128 the step's 4 blocks
    are one trip."""
    assert _planned(*shapes) == plan


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_gated_norm_norms_each_group_on_its_own(groups):
    """``y * silu(z)`` first, then every group's channels over their own
    root mean square, under one weight of the whole width — written out
    here.  One group is the function as it was, to the bit."""
    rng = np.random.default_rng(groups)
    y, z = (jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
            for _ in range(2))
    # groups of unlike scale: a norm over the whole width would show
    y = y * jnp.repeat(jnp.asarray(rng.uniform(0.1, 10.0, 8)), 8)
    w = jnp.asarray(rng.uniform(0.5, 1.5, 64), jnp.float32)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(
        2, 5, groups, 64 // groups)
    want = (gated / np.sqrt(np.mean(gated ** 2, -1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 64) * np.asarray(w)
    got = gated_rms_norm(y, z, w, 1e-5, groups)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
    whole = rms_norm(y * jax.nn.silu(z), w, 1e-5)
    if groups == 1:
        np.testing.assert_array_equal(got, whole)
        np.testing.assert_array_equal(gated_rms_norm(y, z, w, 1e-5), whole)
    else:
        assert _rms_apart(got, whole) > 0.1
    low = gated_rms_norm(y.astype(jnp.bfloat16), z.astype(jnp.bfloat16), w,
                         1e-5, groups)
    assert low.dtype == jnp.bfloat16 and _rms_apart(low, want) < 2e-2


def _norm_written_out(y, z, w, eps, groups):
    """The gated norm by groups as the test above spells it, float32, for
    autodiff: the yardstick of the rule's written-out backward pass."""
    f32 = jnp.float32
    gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(
        *y.shape[:-1], groups, -1)
    return (gated / jnp.sqrt(jnp.mean(gated ** 2, -1, keepdims=True) + eps)
            ).reshape(y.shape) * w


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape,groups,kernels", [
    ((2, 5, 64), 2, False), ((2, 5, 64), 8, False),
    ((2, 600, 256), 2, True), ((2, 600, 4096), 8, True),
], ids=["xla-2-groups", "xla-8-groups", "kernels-2-groups",
        "kernels-nemotron-h-width"])
def test_the_grouped_norm_and_its_written_out_gradients(shape, groups,
                                                        kernels, dtype, tol):
    """Value, ``dy``, ``dz`` and ``dweight`` of the rule against autodiff
    of the written-out norm, in both forms: the XLA one at widths that
    tile nothing and the Pallas kernels (interpreted here) where a
    group's channels fill lane tiles — Nemotron-H's published 4096 in 8
    groups — at 1200 tokens, which the tile of 1024 rows does not divide
    (the weight's gradient leaves the overhang out).  Groups of unlike
    scale: a norm over the whole width is a tenth and more away."""
    width = shape[-1]
    assert norm_kernels_fit(width, groups) is kernels
    rng = np.random.default_rng(width + groups)
    y, z, dout = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                  for _ in range(3))
    y = (y * jnp.repeat(jnp.asarray(rng.uniform(0.1, 10.0, groups)),
                        width // groups)).astype(dtype)
    z, dout = z.astype(dtype), dout.astype(dtype)
    w = jnp.asarray(rng.uniform(0.5, 1.5, width), jnp.float32)

    def both(norm, n):
        out, vjp = jax.vjp(lambda *t: norm(*t, 1e-5, n), y, z, w)
        return (out, *vjp(dout.astype(out.dtype)))

    got, want, whole = (both(gated_rms_norm, groups),
                        both(_norm_written_out, groups),
                        both(_norm_written_out, 1))
    for name, g, t, o in zip(("out", "dy", "dz", "dweight"), got, want,
                             whole):
        assert g.shape == t.shape and g.dtype == (
            jnp.float32 if name == "dweight" else dtype), name
        assert _rms_apart(g, t) < tol, (name, _rms_apart(g, t))
        assert _rms_apart(o, t) > 0.1, name


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_published_sizes_take_the_kernels(dtype, tol, monkeypatch):
    """granite-4.0-h-micro's mixer (heads of 64, state 128, one group,
    chunks of 256) at 4 heads and 600 positions, which the chunk does not
    divide: ``ssd_chunked`` takes the Pallas kernels (interpreted here),
    and values and the six gradients are the recurrence's and the XLA
    form's — in bfloat16 within its rounding, the gradients to the decays
    (``dt``, ``a``) too, which are differences of sums over a chunk."""
    calls = _records_the_kernels(monkeypatch)
    args = _scan_inputs(600, seed=4, batch=1, heads=4, p=64, groups=1, n=128)
    cast = tuple(t.astype(dtype) if i in (0, 3, 4) else t
                 for i, t in enumerate(args))
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=args[0].shape), jnp.float32)
    with HIGHEST:
        want, want_grads = _value_and_grads(ssd_reference, args, weight)
        out = [_value_and_grads(f, cast, weight)
               for f in (lambda *t: ssd_chunked(*t, chunk=256),
                         lambda *t: ssd_xla(*t, chunk=256))]
    assert calls and all(shape == (1, 600, 4, 64) for shape in calls)
    for got, grads in out:
        assert got.shape == want.shape and got.dtype == dtype
        assert _rms_apart(got, want) < tol
        for name, g, w in zip("x dt a b c d".split(), grads, want_grads):
            assert _rms_apart(g, w) < tol, name


@pytest.mark.parametrize("fsdp", [0, 2], ids=["one-device", "fsdp2"])
def test_a_mixer_of_several_groups_through_the_kernels_is_the_xla_forms(
        fsdp, monkeypatch):
    """The block (``blocks/mamba.py::_apply``) at 8 heads x 64 in 4 groups,
    state 128, chunks of 128, 256 tokens: the convolution's split hands
    the scan B and C with their groups side by side and the kernels cut
    them there.  Output and every parameter's gradient against the same
    call with ``ssd_xla`` patched in — on one device, and per shard of the
    batch under an fsdp=2 mesh (``batch_shard_map``)."""
    from ray_tpu.models.blocks.base import Ctx
    from ray_tpu.models.llama import _make_cst

    cfg = _cfg(num_layers=1, layer_types=["mamba"], embed_dim=128,
               ssm_heads=8, ssm_head_dim=64, ssm_groups=4, ssm_state=128,
               ssm_chunk=128, max_seq_len=256)
    assert kernels_fit(8, 64, 4, 128, 128)
    layer = _drawn(init_params(jax.random.PRNGKey(3), cfg))["layers"]
    lp = {k: layer[k][0] for k in mamba.BLOCK.shapes(cfg)}
    rng = np.random.default_rng(9)
    x, weight = (jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
                 for _ in range(2))
    mesh = make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2]
                     ) if fsdp else None
    ctx = Ctx(cfg, mesh, _make_cst(mesh, None), False)

    def run(lp, x):
        out, _ = mamba.BLOCK.apply(ctx, x, {}, lp)
        return jnp.sum(out * weight), out

    both = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))
    calls = _records_the_kernels(monkeypatch)
    with HIGHEST:
        (_, got), got_grads = both(lp, x)
        assert calls == [(2 // (fsdp or 1), 256, 8, 64)]
        monkeypatch.setattr(mamba, "ssd_chunked", ssd_xla)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            run, argnums=(0, 1), has_aux=True))(lp, x)
    assert len(calls) == 1
    assert _rms_apart(got, want) < 1e-5
    names = sorted(lp) + ["x"]
    flat = lambda g: [g[0][k] for k in sorted(lp)] + [g[1]]  # noqa: E731
    for name, g, w in zip(names, flat(got_grads), flat(want_grads)):
        assert g.shape == w.shape and _rms_apart(g, w) < 1e-4, name


def test_the_grouped_norm_per_shard_of_the_batch_sums_its_weights_gradient():
    """Under a mesh the block runs the norm's kernels per shard of the
    batch as it runs the scan's (``batch_shard_map``): the layer's output
    and the gradients to ``gate_norm`` — each shard's tiles sum their own
    rows, the region's transpose adds the shards' — and to ``x`` on an
    fsdp=2 mesh are the one-device ones."""
    from ray_tpu.models.blocks.base import Ctx
    from ray_tpu.models.llama import _make_cst

    cfg = _cfg(num_layers=1, layer_types=["mamba"], embed_dim=128,
               ssm_heads=8, ssm_head_dim=64, ssm_groups=4, ssm_state=128,
               ssm_chunk=128, max_seq_len=256)
    assert norm_kernels_fit(cfg.ssm_inner, cfg.ssm_groups)
    layer = _drawn(init_params(jax.random.PRNGKey(3), cfg))["layers"]
    lp = {k: layer[k][0] for k in mamba.BLOCK.shapes(cfg)}
    rng = np.random.default_rng(11)
    x, weight = (jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
                 for _ in range(2))

    def grads(mesh):
        ctx = Ctx(cfg, mesh, _make_cst(mesh, None), False)
        run = lambda lp, x: jnp.sum(  # noqa: E731
            mamba.BLOCK.apply(ctx, x, {}, lp)[0] * weight)
        with HIGHEST:
            value, (d_lp, dx) = jax.jit(jax.value_and_grad(
                run, argnums=(0, 1)))(lp, x)
        return value, d_lp["gate_norm"], dx

    want = grads(None)
    got = grads(make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2]))
    for name, g, w in zip(("value", "gate_norm", "x"), got, want):
        assert g.shape == w.shape and _rms_apart(g, w) < 1e-5, name


@pytest.mark.parametrize("heads,head_dim,groups,state,chunk,fits", [
    (64, 64, 1, 128, 256, True),      # granite-4.0-h-micro
    (48, 128, 1, 128, 256, True),     # a head fills the lanes
    (8, 32, 1, 256, 128, True),       # four heads a block
    (64, 64, 8, 128, 256, True),      # four head blocks in each group
    (64, 64, 8, 128, 128, True),      # Nemotron-H
    (8, 64, 8, 128, 128, False),      # a group is half a head block
    (64, 64, 3, 128, 128, False),     # groups that do not divide the heads
    (63, 64, 1, 128, 256, False),     # half a block left over
    (64, 48, 1, 128, 256, False),     # heads straddle the lanes
    (64, 8, 1, 128, 256, False),      # sixteen heads a block
    (64, 64, 1, 64, 256, False),      # the state half fills a tile
    (64, 64, 1, 128, 192, False),     # a sequence of 192, or such a chunk
    (8, 16, 2, 8, 8, False),          # the tests' models
], ids=lambda v: str(v))
def test_the_shape_rule_that_picks_the_kernels(heads, head_dim, groups,
                                               state, chunk, fits):
    assert kernels_fit(heads, head_dim, groups, state, chunk) is fits


def test_the_kernels_refuse_a_head_block_that_straddles_two_groups():
    """``ssd_chunked`` never sends them such a call (``kernels_fit``); a
    direct one is refused rather than answered with another group's B."""
    args = _scan_inputs(16, heads=4, p=8, groups=2)
    with pytest.raises(ValueError, match="straddles"):
        ssd_kernels(*args, chunk=8)


def test_causal_conv1d_is_the_direct_sum_and_causal():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 11, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    bias = rng.normal(size=(6,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(x.shape[1]):
        for i in range(4):
            if t - 3 + i >= 0:  # w[3] meets the current token
                want[:, t] += w[i] * x[:, t - 3 + i]
    want = want + bias
    want = want / (1 + np.exp(-want))
    got = causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # a later token changes no earlier output
    later = x.copy()
    later[:, 7:] += 1.0
    moved = causal_conv1d(jnp.asarray(later), jnp.asarray(w),
                          jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(moved)[:, :7],
                                  np.asarray(got)[:, :7])
    assert not np.allclose(np.asarray(moved)[:, 7], np.asarray(got)[:, 7])

    # the written-out backward pass against autodiff of the direct sum
    def direct(x, w, bias):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jax.nn.silu(bias + sum(padded[:, i:i + x.shape[1]] * w[i]
                                      for i in range(4)))

    weight = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    args = (jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias))
    grads, want_grads = (jax.grad(lambda *t: jnp.sum(f(*t) * weight),
                                  argnums=(0, 1, 2))(*args)
                         for f in (causal_conv1d, direct))
    for g, wg in zip(grads, want_grads):
        np.testing.assert_allclose(g, wg, atol=1e-5, rtol=1e-5)


# ------------------------------------------------- the hybrid model ------

TOKENS = ROWS["granite"].tokens
_cfg = functools.partial(tiny_models.tiny, "granite")
_drawn = functools.partial(tiny_models.drawn, seed=5, also=("D",))
LOSS_TOL, GRAD_TOL = 2e-6, 2e-4   # float32 against float32, relative


def test_hybrid_loss_and_gradients_equal_the_plain_reference():
    """mamba, attention, mamba through ``loss_fn`` (the flash kernel
    interpreted, the layer checkpoint on) against the benchmark's
    reference, which computes the recurrence a token at a time: the loss
    within 2e-6, every gradient leaf within 2e-4 of its scale — the tied
    table's too, whose gradient is the sum of both of its uses."""
    ours = program("granite")
    assert ours.cfg.layer_runs == (
        ("mamba", 1), ("attention", 1), ("mamba", 1))
    assert "lm_head" not in ours.params and len(ours.params["layers"]) == 3
    against_the_reference("granite", parts=(), rtol=LOSS_TOL, nll_atol=None,
                          grad_rtol=GRAD_TOL)


def _no_d(params):
    return dict(params, layers=tuple(
        dict(run, D=jnp.zeros_like(run["D"])) if "D" in run else run
        for run in params["layers"]))


@pytest.mark.parametrize("wrong", [
    dict(embedding_multiplier=1.0), dict(residual_multiplier=1.0),
    dict(logits_scaling=1.0), dict(position_embedding="rope"),
    dict(attention_multiplier=None), "D dropped", "gate after the norm",
], ids=lambda w: w if isinstance(w, str) else "-".join(
    f"{k}={v}" for k, v in w.items()))
def test_structural_controls_fail_against_the_reference(wrong, monkeypatch):
    """What the comparison must be able to tell from the published
    structure: a multiplier left at 1, RoPE left on, the softmax scale
    1/sqrt(d) in place of the given one, ``D x`` dropped, the gate applied
    after the norm.  Each moves the loss fifty tolerances or more (RoPE,
    the least, a hundred: one small attention layer of three)."""
    cfg = _cfg(attn_impl="reference", remat=False)
    params = program_params = program("granite").params
    # before any patch: the reference's answer is kept for the process
    want = float(reference("granite").parts["total"])
    if wrong == "D dropped":
        program_params = _no_d(params)
    elif wrong == "gate after the norm":
        monkeypatch.setattr(
            mamba, "gated_rms_norm", lambda y, z, w, eps, *_: rms_norm(
                y, w, eps) * jax.nn.silu(z))
    else:
        cfg = dataclasses.replace(cfg, **wrong)
    with HIGHEST:
        loss = float(jax.jit(lambda p: loss_fn(p, {"tokens": TOKENS}, cfg)[0])(
            program_params))
    assert abs(loss - want) / want > 50 * LOSS_TOL, (loss, want)


def test_layer_types_runs_and_hashing():
    published = (["mamba"] * 5 + ["attention"] + ["mamba"] * 9
                 + ["attention"] + ["mamba"] * 9 + ["attention"]
                 + ["mamba"] * 9 + ["attention"] + ["mamba"] * 4)
    whole = LlamaConfig(num_layers=40, layer_types=published, ssm_heads=64)
    assert [n for _, n in whole.layer_runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    cut = dataclasses.replace(whole, num_layers=10)  # the first entries
    assert cut.layer_runs == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert isinstance(cut.layer_types, tuple) and hash(cut) != hash(whole)
    assert LlamaConfig(num_layers=3).layer_runs == (("attention", 3),)
    assert LlamaConfig(num_layers=2, layer_types=["mamba"] * 2, ssm_heads=2
                       ).layer_runs == (("mamba", 2),)
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig(num_layers=2, layer_types=["mamba", "hyena"])
    with pytest.raises(ValueError, match="num_layers"):
        LlamaConfig(num_layers=3, layer_types=["mamba", "attention"])


# Recorded on the parent commit (5579c54), where this tree gave the same
# bits: loss and the sum of absolute gradients of LlamaConfig.tiny(**kw)
# from PRNGKey(0) on the tokens below, and the sum of the parameters.  (The
# last bit of a sum follows the host's thread count: one device read
# 0x1.7c49de p+2, the tests' eight 0x1.7c49dc p+2; hence the 1e-6.)
PARENT = [
    (dict(), "0x1.7c49dc0000000p+2", "0x1.b999100000000p+8",
     "0x1.06f5080000000p+9"),
    (dict(num_experts=4, num_selected=2, z_loss_coef=0.001, qk_norm=True),
     "0x1.8989560000000p+2", "0x1.db375a0000000p+8", "0x1.01ee1e0000000p+9"),
    (dict(attn_impl="flash", remat=True, num_kv_heads=2),
     "0x1.7593960000000p+2", "0x1.a424b60000000p+8", "0x1.0c9c9c0000000p+9"),
]


@pytest.mark.parametrize("kw,loss,grads,weights", PARENT,
                         ids=["dense", "moe-qknorm", "gqa-flash-remat"])
def test_existing_models_are_equal_to_the_parent(kw, loss, grads, weights):
    """A model of one kind of layer keeps its parameter tree, its
    initialisation and its arithmetic: values recorded on the parent."""
    cfg = LlamaConfig.tiny(**kw)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (2, 33), dtype=np.int32))
    got, g = jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    total = lambda tree, f: float(sum(jnp.sum(f(a))
                                      for a in jax.tree.leaves(tree)))
    assert (float(got), total(g, jnp.abs), total(params, lambda a: a)) == \
        pytest.approx([float.fromhex(v) for v in (loss, grads, weights)],
                      rel=1e-6)


def test_existing_models_parameter_trees_and_axes_are_unchanged():
    dense = {"attn_norm": ("layer", "embed"),
             "wq": ("layer", "kernel_in", "heads"),
             "wk": ("layer", "kernel_in", "kv_heads"),
             "wv": ("layer", "kernel_in", "kv_heads"),
             "wo": ("layer", "heads", "kernel_in"),
             "mlp_norm": ("layer", "embed"),
             "w_gate": ("layer", "kernel_in", "mlp"),
             "w_up": ("layer", "kernel_in", "mlp"),
             "w_down": ("layer", "mlp", "kernel_in")}
    top = {"embed": ("vocab", "kernel_in"), "final_norm": ("embed",),
           "lm_head": ("kernel_in", "vocab")}
    assert param_logical_axes(LlamaConfig.tiny()) == dict(top, layers=dense)
    moe = dict(dense, q_norm=("layer", "heads"), k_norm=("layer", "kv_heads"),
               router=("layer", "kernel_in", None),
               w_gate=("layer", "expert", "kernel_in", "mlp"),
               w_up=("layer", "expert", "kernel_in", "mlp"),
               w_down=("layer", "expert", "mlp", "kernel_in"))
    cfg = LlamaConfig.tiny(num_experts=4, qk_norm=True)
    assert param_logical_axes(cfg) == dict(top, layers=moe)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert set(shapes) == set(top) | {"layers"}
    assert {k: v.shape for k, v in shapes["layers"].items()} == {
        "attn_norm": (2, 64), "mlp_norm": (2, 64), "q_norm": (2, 64),
        "k_norm": (2, 64), "wq": (2, 64, 64), "wk": (2, 64, 64),
        "wv": (2, 64, 64), "wo": (2, 64, 64), "router": (2, 64, 4),
        "w_gate": (2, 4, 64, 128), "w_up": (2, 4, 64, 128),
        "w_down": (2, 4, 128, 64)}


def test_mamba_initialisation_is_the_reference_codes():
    """A in 1..16, dt in 1e-3..1e-1 through the inverse softplus, D = 1,
    the convolution within 1/sqrt(width); the tied table as a head."""
    cfg = _cfg(ssm_heads=64, ssm_head_dim=4, param_dtype=jnp.float32)
    run = init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
    a = np.exp(np.asarray(run["A_log"]))
    assert 1.0 <= a.min() < 3.0 and 14.0 < a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(run["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() < 3e-3 and 3e-2 < dt.max() <= 0.1 * 1.01
    assert np.all(np.asarray(run["D"]) == 1.0)
    for name in ("conv_w", "conv_b"):
        w = np.asarray(run[name])
        assert -0.5 <= w.min() < -0.4 and 0.4 < w.max() <= 0.5
    assert np.all(np.asarray(run["gate_norm"]) == 1.0)
    table = np.asarray(init_params(jax.random.PRNGKey(1), cfg)["embed"])
    assert table.std() == pytest.approx(64 ** -0.5, rel=0.05)


def test_hybrid_on_an_fsdp2_mesh_equals_one_device():
    """``init_train_state(mesh=...)`` shards the new parameters by their
    logical axes, and the loss on fsdp=2 is the one-device loss."""
    import optax

    cfg, opt = _cfg(remat=False), optax.adam(1e-2)
    mesh = make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2])
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, mesh=mesh)
    shardings = train_state_shardings(cfg, opt, mesh)
    mamba = shardings.params["layers"][0]
    assert mamba["ssm_in"].spec == jax.sharding.PartitionSpec(
        None, "fsdp", None)
    assert mamba["ssm_out"].spec == jax.sharding.PartitionSpec(
        None, None, "fsdp")
    assert state.params["layers"][0]["ssm_in"].sharding == mamba["ssm_in"]
    assert shardings.opt_state[0].mu["layers"][2]["conv_w"] == \
        shardings.params["layers"][2]["conv_w"]
    single = init_params(jax.random.PRNGKey(0), cfg)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
                 jax.device_get(state.params), jax.device_get(single))
    with HIGHEST:
        sharded = jax.jit(lambda p, t: loss_fn(p, {"tokens": t}, cfg,
                                               mesh=mesh)[0])(
            state.params, TOKENS)
        one = loss_fn(single, {"tokens": TOKENS}, cfg)[0]
    assert float(sharded) == pytest.approx(float(one), rel=1e-5)


def test_pipelines_refuse_a_mixed_pattern():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshConfig(pp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="layers differ"):
        forward_pipelined(params, TOKENS[:, :-1], cfg, mesh=mesh,
                          num_microbatches=2)
    with pytest.raises(NotImplementedError, match="layers differ"):
        make_pipeline_stage_fn(cfg)
    with pytest.raises(NotImplementedError, match="tied head"):
        pipeline_stage_params(params, 2)


def test_a_one_row_batch_takes_the_loss_of_the_batched_form():
    """A one-row batch is no case of its own: the same loss and gradients
    as the same row twice.  (Until PR 82 ``_mean_nll`` dropped the
    degenerate dimension, because on the TPU the gradient of the loss's
    gather over it compiled to a flat scatter, 5 GB at 8192 x 100352;
    ``_row_nll`` gathers nothing now and its gradient is written out.)"""
    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(2), cfg)
    row = TOKENS[:1, :33]
    loss = lambda t: jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": t}, cfg)[0])(params)
    (one, one_grads), (two, two_grads) = loss(row), loss(
        jnp.concatenate([row, row]))
    assert float(one) == pytest.approx(float(two), rel=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-7), one_grads, two_grads)
