"""The mixers' causal convolution + SiLU as its Pallas pair
(``ops/ssm.py``: ``causal_conv_fwd`` / ``causal_conv_bwd``, interpreted
here) against ``conv_xla``, the XLA form of the same rule, which runs
wherever the shapes do not tile the chip and is the oracle: values and the gradients
to x, weight and bias, across a tile's edges in both directions, across
the rows of a batch, per shard of the batch, and the shape rule that
routes a call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.ops.ssm import causal_conv1d, conv_kernels_fit
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import batch_shard_map


def _small_tiles(monkeypatch, tokens_last=False):
    """Tiles of 32 sublanes x 256 lanes at most in windows of 16 x 128,
    so that a test's few tokens and channels are several tiles and channel
    blocks of two windows each way (tile and window are static arguments
    of the calls: no compiled program is shared with the published
    sizes')."""
    monkeypatch.setattr(ssm, "_CONV_TILE", ((32, 256), (256, 32)))
    monkeypatch.setattr(ssm, "_CONV_WINDOW", ((16, 128), (128, 16)))


@pytest.fixture
def small_tiles(monkeypatch):
    _small_tiles(monkeypatch)


def _inputs(batch, rows, channels, taps, biased, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, rows, channels)), dtype)
    w = jnp.asarray(rng.normal(size=(taps, channels)) * 0.5, jnp.float32)
    bias = (jnp.asarray(rng.normal(size=(channels,)), jnp.float32)
            if biased else None)
    dy = jnp.asarray(rng.normal(size=(batch, rows, channels)), jnp.float32)
    return x, w, bias, dy


def _value_and_grads(conv, x, w, bias, dy):
    """The weighted sum of ``conv``'s output and its gradients to x,
    weight and (where there is one) bias, under one jit."""
    def loss(x, w, bias):
        return jnp.sum(conv(x, w, bias).astype(jnp.float32) * dy)

    argnums = (0, 1) if bias is None else (0, 1, 2)
    value, grads = jax.jit(jax.value_and_grad(loss, argnums))(x, w, bias)
    return (value,) + grads


def _kernel_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("batch,sublanes,lanes", [
    (1, 32, 256),     # ONE tile: two windows each way
    (2, 96, 768),     # three tiles a row of the batch, three channel blocks
], ids=["one-tile", "tiles-and-rows"])
@pytest.mark.parametrize("tokens_last", [False, True],
                         ids=["tokens-first", "tokens-last"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_pair_is_the_xla_form(monkeypatch, taps, biased, dtype, tol,
                                  tokens_last, batch, sublanes, lanes):
    """Values and the gradients to x, weight and bias: the ``k - 1``
    tokens across a tile's edge (behind it forward, behind AND ahead
    backward), zeros before a sequence and no gradient past its end, in
    every row of the batch, the weight's and the bias's sums over every
    tile — the array standing either way, tokens down the sublanes (a
    Mamba-2 mixer's) or along the lanes (the delta rules': the pair walks
    the transposed view)."""
    _small_tiles(monkeypatch, tokens_last)
    rows, channels = (lanes, sublanes) if tokens_last else (sublanes, lanes)
    args = _inputs(batch, rows, channels, taps, biased, dtype)
    conv = lambda *t: causal_conv1d(  # noqa: E731
        *t, tokens_last=tokens_last)
    assert _kernel_calls(conv, *args[:3]) == 1
    got = _value_and_grads(conv, *args)
    assert _kernel_calls(ssm.conv_xla, *args[:3]) == 0
    want = _value_and_grads(ssm.conv_xla, *args)
    for name, g, w in zip(("value", "dx", "dw", "dbias"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=tol * scale, rtol=0, err_msg=name)


@pytest.mark.parametrize("tokens_last", [False, True],
                         ids=["tokens-first", "tokens-last"])
def test_a_row_of_the_batch_sees_zeros_not_its_neighbours_tail(monkeypatch,
                                                               tokens_last):
    """A tile never reads across two rows of the batch: each row's output
    and gradient are what the row alone gives, to the bit — forward the
    second row's first tokens see zeros, not the first row's last;
    backward the first row's last tokens collect nothing from the
    second's first."""
    _small_tiles(monkeypatch, tokens_last)
    x, w, bias, dy = _inputs(2, 512 if tokens_last else 64, 256, 4, True,
                             jnp.float32, seed=1)

    def both(x, dy):
        y, back = jax.vjp(lambda t: causal_conv1d(
            t, w, bias, tokens_last=tokens_last), x)
        return y, back(dy)[0]

    y, dx = jax.jit(both)(x, dy)
    for row in range(2):
        y_row, dx_row = jax.jit(both)(x[row:row + 1], dy[row:row + 1])
        np.testing.assert_array_equal(y[row], y_row[0])
        np.testing.assert_array_equal(dx[row], dx_row[0])


@pytest.mark.parametrize("channels,taps,rows,tokens_last,fits", [
    (12288, 4, 8192, True, True),     # Kimi-Linear
    (24576, 4, 4096, True, True),     # Solar-Open2
    (11520, 4, 4096, True, True),     # Olmo-Hybrid
    (6144, 4, 8192, False, True),     # Nemotron-H
    (4352, 4, 8192, False, True),     # granite: 34 lane tiles
    (10240, 4, 4096, False, True),    # Nemotron-3 Super
    (128, 1, 16, False, True),
    (96, 4, 64, False, False),        # no lane multiple
    (128, 4, 11, False, False),       # an odd length
    (128, 4, 24, False, False),       # float32 tiles, half a bfloat16 one
    (128, 9, 64, False, False),       # more taps than a sublane tile's rows
    (48, 4, 128, True, True),         # tokens-last: the TOKENS fill lanes
    (128, 4, 64, True, False),        # ... and 64 do not
    (24, 4, 128, True, False),        # ... nor 24 channels sublane tiles
])
def test_the_shape_rule_that_routes_the_convolution(channels, taps, rows,
                                                    tokens_last, fits):
    assert conv_kernels_fit(channels, taps, rows, tokens_last) is fits
    if rows <= 128:  # what the rule says is what a call does
        x, w, _, _ = _inputs(1, rows, channels, taps, False, jnp.float32)
        conv = lambda x, w: causal_conv1d(  # noqa: E731
            x, w, tokens_last=tokens_last)
        assert _kernel_calls(conv, x, w) == int(fits)
        assert _kernel_calls(jax.grad(  # forward and backward
            lambda x, w: conv(x, w).sum(), (0, 1)), x, w) == 2 * fits


@pytest.mark.parametrize("shape,tokens_last,tile,window", [
    ((1, 8192, 12288), True, (2048, 256), (2048, 64)),    # Kimi-Linear
    ((1, 4096, 11520), True, (2048, 256), (2048, 64)),    # Olmo-Hybrid
    ((2, 8192, 6144), False, (2048, 512), (64, 256)),     # Nemotron-H
    ((1, 8192, 4352), False, (2048, 256), (64, 256)),     # granite
    ((1, 48, 640), False, (48, 128), (48, 128)),
])
def test_the_tile_is_chosen_from_the_shapes(shape, tokens_last, tile, window):
    """A tile's ``(tokens, channels)`` and a window's: the largest that
    divide the array and the tile — granite's 34 lane tiles go two at a
    time, a window is whole hardware tiles of its tile."""
    static = ssm._conv_static(jax.ShapeDtypeStruct(shape, jnp.bfloat16),
                              tokens_last)
    assert (static["axis"], static["tile"], static["sub"]) == (
        int(tokens_last), tile, window)


@pytest.mark.parametrize("biased,tokens_last", [(True, False), (False, True)],
                         ids=["bias-tokens-first", "no-bias-tokens-last"])
def test_per_shard_of_the_batch_is_the_one_device_call(biased, tokens_last):
    """Under a mesh a block runs the pair per shard of the batch
    (``batch_shard_map``, as the scan's and the norm's kernels): on fsdp=2
    x tp=2 the output and the gradients are the one-device call's — the
    weight's and the bias's are each shard's own sums added by the
    region's transpose — to float32 rounding (the CPU's compiler contracts
    the interpreted body's multiply-adds by the program's shape, so not to
    the bit)."""
    import functools

    x, w, bias, dy = _inputs(2, 128, 256, 4, biased, jnp.float32, seed=2)
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    ranks = (3, None, None) if biased else (3, None)
    conv = functools.partial(causal_conv1d, tokens_last=tokens_last)
    sharded = batch_shard_map(conv, mesh, ranks, 3)
    per_shard = lambda x, w, bias: (  # noqa: E731
        sharded(x, w, bias) if biased else sharded(x, w))

    def out_and_grads(conv):
        y, back = jax.vjp(conv, x, w, bias)
        return (y,) + tuple(g for g in back(dy) if g is not None)

    want = jax.jit(lambda: out_and_grads(conv))()
    got = jax.jit(lambda: out_and_grads(per_shard))()
    assert len(got) == len(want) == 3 + biased
    for name, g, t in zip(("y", "dx", "dw", "dbias"), got, want):
        np.testing.assert_allclose(g, t, rtol=1e-5, atol=2e-6, err_msg=name)
