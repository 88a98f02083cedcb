"""State-space mixer ops: what a Mamba-2 layer computes between its two
projections (Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060).

Per head, with a scalar decay a token, the layer is the recurrence

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        (head_dim x state)
    y_t = H_t C_t + D x_t

``ssd_chunked`` computes it in chunks (the paper's SSD form): inside a
chunk the outputs are ``(C B^T o L) (dt x)`` with ``L[i, j] = exp(sum of
dt A over j < k <= i)`` for ``j <= i`` and 0 above the diagonal — a masked
matmul, which is what the MXU is for — and ONE state a chunk is carried
across chunks.  ``ssd_reference`` is the recurrence itself, a token at a
time in float32: what the tests hold the chunked form to, as
``ops/attention.py`` has ``mha_reference`` beside its kernels.

Two forms of ONE algorithm, chosen by what a call's shapes show
(``kernels_fit``): where heads, state and chunk tile the chip — the head
size divides the 128 lanes and is at least 16, the heads of each GROUP
(B and C are a group's, shared by its ``heads / groups`` heads) fill whole
lane blocks, the state and the chunk are multiples of 128: granite's
published 64 heads x 64 in one group, state 128, chunks of 256;
Nemotron-H's 64 x 64 in 8 groups, chunks of 128 — ``ssd_kernels``, two
Pallas kernels under a ``custom_vjp`` (interpreted off the chip, so the
tests run the same code); elsewhere (the tests' tiny models, a head size
that straddles the lanes, a group of less than a lane block)
``ssd_xla``, the same sums as plain XLA differentiated by autodiff, which
writes the intra-chunk matrices (``(chunks, heads, chunk, chunk)``: 268 MB
in bfloat16 at 8192 tokens) to memory in every pass.  ``ssd_xla`` is also
the tests' second oracle.

The kernels (``ssd_fwd``, ``ssd_bwd``): grid ``(batch, chunk, head
block)``, a head block being the heads that fill 128 lanes of the ``(b, s,
heads * head_dim)`` layout the model's projections read and write, so
nothing is transposed around a call.  A grid step's head blocks lie in ONE
group: B and C come as ``(b, s, groups * state)``, the groups side by side
as the convolution's split leaves them, and a step's block of them is cut
at its group — nothing is repeated to the heads; backward, a group's
``dB`` and ``dC`` are summed over its steps in VMEM and written at its
last.  The chunk axis is sequential and the state of every head rides in
a VMEM scratch from one chunk to the next (backward: its gradient, chunks
in reverse); the chunk's ``C B^T o L`` matrix is made, used and dropped in
VMEM.  ``ssd_fwd`` also writes the
state ENTERING each chunk (float32, 67 MB a layer at the published
sizes), which is all ``ssd_bwd`` needs beside the inputs: under a layer
checkpoint the forward kernel runs again in the backward pass and its
states never reach the checkpoint's stack.  The cumulative log-decays of
a chunk are made by XLA around the call (small ``(b, s, heads)`` float32
arrays), which also differentiates them: the backward kernel returns the
gradient to the cumulative sums (row sums less column sums of ``dM o M``,
what the decays to and from the chunk's ends collect, and ``<dH, H>`` at
a chunk's last token).

Beside them the two short causal convolutions of the recurrent mixers,
their backward passes written out: ``causal_conv1d`` (depthwise, SiLU
fused: Mamba-2's and both delta rules') — where the channels fill lane
tiles and the rows sublane tiles (``conv_kernels_fit``: every published
width) the Pallas pair ``causal_conv_fwd`` / ``causal_conv_bwd`` over
``(rows, lanes)`` tiles of ``(b, s, c)`` as it stands, which makes a
tile's ``pre``, sigmoid and ``dpre`` once in VMEM, elsewhere ``conv_xla``,
plain XLA — and ``gated_short_conv`` (``C * conv(B * x)``, no activation:
LFM2's whole mixer between its two projections), plain XLA over the
helpers it shares with ``conv_xla``; and Mamba-2's output norm,
``gated_rms_norm``: one group is plain XLA, several are one rule with a
written-out backward pass — the kernels ``gated_norm_fwd`` /
``gated_norm_bwd`` over the ``(tokens, inner)`` arrays as they stand where
a group's channels fill lane tiles (``norm_kernels_fit``).

Precision: the decays (``dt A``, their cumulative sums, every ``exp``) and
the state carried across chunks are float32; the operands of the big
products (``C B^T``, the masked matrix times ``dt x``, ``B^T`` times the
decayed ``dt x``, ``C`` times the entering state) are in ``x.dtype`` with
float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.layers import rms_norm

_F32 = jnp.float32
_LANES = 128          # a head block fills the TPU's lane width
_MAX_BLOCK_HEADS = 8  # a block's heads are written out in the kernel body
# Head blocks a grid step works through, at most: fewer, longer steps (TPU
# v5e, one granite layer: forward 0.83 ms with 1, 0.78 with 4, 0.74 with 8;
# PERF.md §6, PR 32), their B, C and C B^T made once.
_STEP_BLOCKS = 8
# The ``(q, q)`` elements of a head a trip of a step's loop over its blocks
# covers, at least: a block of granite's chunks of 256 is a trip, while at
# chunks of 128 a block is too short to hide its own latencies (TPU v5e, a
# Nemotron-H layer's forward call: 3.32 ms with 1 block a trip, 2.91 with
# 2, 2.48 with all 4 of the step; PERF.md §6, PR 49).
_TRIP_ELEMENTS = 256 * 256


def _conv_pre(x, weight, bias):
    """The convolution before its SiLU, float32: ``bias + sum_i weight[i]
    * x[t - (k-1) + i]`` (no ``bias`` where it is None).  The shifted
    copies are cut from ``x`` in its own dtype and widened inside the sum,
    so no float32 copy of ``x`` is written."""
    k, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    lead = None if bias is None else bias.astype(_F32)
    taps = sum(padded[:, i:i + s].astype(_F32) * w[i] for i in range(k))
    return taps if lead is None else lead + taps


def causal_conv1d(x: jax.Array, weight: jax.Array,
                  bias: Optional[jax.Array] = None, *,
                  tokens_last: bool = False) -> jax.Array:
    """SiLU of the causal depthwise convolution of ``x (b, s, c)`` along
    ``s``: ``y[t] = bias + sum_i weight[i] * x[t - (k-1) + i]`` with
    ``weight (k, c)`` and zeros before the sequence — ``weight[k-1]``
    meets the current token, as a torch ``Conv1d(groups=c, padding=k-1)``
    cut to ``s`` outputs has it; ``bias`` None is a convolution without
    one.  Float32 inside, ``x.dtype`` out.

    Two forms of one rule, chosen by what the call's shapes show
    (``conv_kernels_fit``): where the array tiles the chip,
    ``conv_kernels`` — the Pallas pair ``causal_conv_fwd`` /
    ``causal_conv_bwd`` over 2-D tiles of the array as it stands,
    interpreted off the chip; elsewhere ``conv_xla``, which is also the
    tests' oracle.  ``tokens_last`` says HOW it stands, which the caller
    knows and the shapes do not show: a mixer whose rule reads ``(b,
    heads, d, s)`` (the delta rules) is laid out by XLA with the tokens
    along the lanes, and the pair then walks the transposed view — the
    values are the same either way.  Either way the backward pass is
    written out and keeps nothing but the arguments: the gradient to ``x``
    is the same convolution run against time.  A Pallas kernel has no
    partitioning rule: under a mesh a block calls this per shard of the
    batch."""
    if conv_kernels_fit(x.shape[2], weight.shape[0], x.shape[1],
                        tokens_last):
        return conv_kernels(x, weight, bias, tokens_last)
    return conv_xla(x, weight, bias)


@jax.custom_vjp
def conv_xla(x, weight, bias):
    """``causal_conv1d`` as plain XLA, for any shapes.  Its backward pass
    is written out (autodiff of pad-and-slice writes one float32 ``(b, s,
    c)`` array a tap and sums them in a second pass) as ONE expression
    that XLA fuses into its consumers: every element of ``dx`` and of
    ``dw`` remakes ``pre``, its sigmoid and ``dpre`` at each tap."""
    return jax.nn.silu(_conv_pre(x, weight, bias)).astype(x.dtype)


def _conv_grads(dpre, x, weight):
    """What ``dpre``, the gradient to ``_conv_pre``'s output, sends to its
    input ``x`` (the same convolution run against time) and to ``weight``
    (``dw_i = sum_t dpre_t x_(t - (k-1) + i)``), both float32."""
    k, s = weight.shape[0], x.shape[1]
    w = weight.astype(_F32)
    ahead = jnp.pad(dpre, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(ahead[:, k - 1 - i:k - 1 - i + s] * w[i] for i in range(k))
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(dpre * padded[:, i:i + s].astype(_F32), (0, 1))
                    for i in range(k)])
    return dx, dw


def _dsilu(pre, dy):
    """The gradient to ``pre`` of ``silu(pre)`` under ``dy``, float32."""
    sig = jax.nn.sigmoid(pre)
    return dy * sig * (1.0 + pre * (1.0 - sig))


def _conv_xla_bwd(res, dy):
    x, weight, bias = res
    pre = _conv_pre(x, weight, bias)          # cheap to run again
    dpre = _dsilu(pre, dy.astype(_F32))
    dx, dw = _conv_grads(dpre, x, weight)
    return (dx.astype(x.dtype), dw.astype(weight.dtype),
            None if bias is None
            else jnp.sum(dpre, (0, 1)).astype(bias.dtype))


conv_xla.defvjp(lambda *args: (conv_xla(*args), args), _conv_xla_bwd)


# ------------------------------------- the convolution's Pallas pair
#
# A grid step is one 2-D tile of the array AS IT STANDS in memory, and it
# stands one of two ways.  A Mamba-2 mixer keeps ``(b, s, c)`` with the
# tokens down the sublanes and the channels along the lanes (the scan's
# kernels read it so).  The delta rules' kernels read ``(b, heads, d, s)``,
# tokens along the LANES, and XLA lays their whole mixer out that way from
# the input projection on: there (``tokens_last``) the pair walks the
# transposed view ``(b, c, s)``, which costs no copy, where a pair fixed to
# ``(b, s, c)`` made XLA turn the array round before and after every call
# (PERF.md §6, PR 67).  ``axis`` below is the tokens' axis of a tile: 0 or
# 1.  The ``k - 1`` tokens before a tile (backward: after it too) come from
# the same array through a second BlockSpec one hardware tile long, zeros
# at a sequence's ends, so a tile never reads across two rows of the batch.
# A grid step works through its tile in windows (forward with time,
# backward against it, a window's edge riding to the next in registers): a
# window is widened to float32 once, its shifts are rotates whose wrapped
# tokens are put right from the neighbour's, and ``pre``, its sigmoid and
# ``dpre`` are made once and never reach HBM.

_CONV_EDGE = (8, _LANES)      # a float32 tile's extent along either axis
_CONV_HALO = (16, _LANES)     # a bfloat16 tile's: the halo blocks' extent
# A tile's (tokens, channels), at most, and those of the windows a grid
# step works through it in, by the tokens' axis: the least forward x 2 +
# backward of a sweep on the v5e (PERF.md §6, PR 67; a layer of 100.7 M
# elements, ms forward / backward).  Tokens down the sublanes, windows of
# sixteen float32 registers whose chain of values never leaves them: 0.73 /
# 1.36 (the tile in one piece 1.17 / 2.27).  Tokens along the lanes a
# window's rotates cross as many registers as it is long, a short or a thin
# window loses (512 x 16: 2.09 / 4.81) and one as long as the tile, 64
# channels at a time, wins: 0.95 / 2.05 (the tile in one piece 1.06 / 2.20).
_CONV_TILE = ((2048, 512), (2048, 256))
_CONV_WINDOW = ((64, 256), (2048, 64))


def conv_kernels_fit(channels: int, taps: int, rows: int,
                     tokens_last: bool = False) -> bool:
    """Whether ``causal_conv1d``'s call tiles the chip: whole lane tiles
    along the lanes' axis (the channels; the tokens where ``tokens_last``),
    whole sublane tiles (bfloat16's 16) along the other, no more taps than
    a float32 sublane tile has rows."""
    lanes, sublanes = (rows, channels) if tokens_last else (channels, rows)
    return (lanes % _LANES == 0 and sublanes % _CONV_HALO[0] == 0
            and 1 <= taps <= _CONV_EDGE[0])


def _largest_tile(size, unit, most):
    """The largest multiple of ``unit`` that divides ``size``, ``most`` at
    most."""
    return max(t for t in range(unit, min(size, most) + 1, unit)
               if size % t == 0)


def _conv_static(x, tokens_last):
    """What the two calls are compiled for, beside their shapes: the
    tokens' axis of a tile, the tile's ``(tokens, channels)`` and those of
    the windows a grid step works through, chosen from the shapes."""
    axis = int(tokens_last)
    units = _CONV_HALO[axis], _CONV_HALO[1 - axis]
    tile = tuple(map(_largest_tile, x.shape[1:], units, _CONV_TILE[axis]))
    return dict(
        axis=axis, tile=tile,
        sub=tuple(map(_largest_tile, tile, units, _CONV_WINDOW[axis])),
        interpret=attention._interpret_default())


def _laid(tokens, channels, axis):
    """``(tokens, channels)`` in the order a tile has them: the tokens
    along ``axis``."""
    return (channels, tokens) if axis else (tokens, channels)


def _span(t, start, stop, axis):
    """``t[start:stop]`` along ``axis`` of a tile (a value or a ref)."""
    return t[_laid(slice(start, stop), slice(None), axis)]


def _behind(tile, before, d, axis):
    """``tile[t - d]`` along the tokens' ``axis`` of a float32 tile, its
    first ``d`` tokens the last of ``before`` (one hardware tile long)."""
    if d == 0:
        return tile
    n, e = tile.shape[axis], before.shape[axis]
    rolled = pltpu.roll(tile, d, axis)
    head = jnp.where(_iota(before.shape, axis) < d,
                     pltpu.roll(before, d, axis), _span(rolled, 0, e, axis))
    if n == e:
        return head
    return jnp.concatenate([head, _span(rolled, e, n, axis)], axis=axis)


def _ahead(tile, after, d, axis):
    """``tile[t + d]``, its last ``d`` tokens the first of ``after``."""
    if d == 0:
        return tile
    n, e = tile.shape[axis], after.shape[axis]
    rolled = pltpu.roll(tile, n - d, axis)
    tail = jnp.where(_iota(after.shape, axis) >= e - d,
                     pltpu.roll(after, e - d, axis),
                     _span(rolled, n - e, n, axis))
    if n == e:
        return tail
    return jnp.concatenate([_span(rolled, 0, n - e, axis), tail], axis=axis)


def _add_all(terms):
    return functools.reduce(lambda a, b: a + b, terms)


def _tile_pre(shifted, w, lead, axis):
    """``_conv_pre`` of a tile from its ``k`` shifted copies (tap ``i``
    meets ``x[t - (k-1) + i]``), the taps in the XLA form's order."""
    taps = _add_all([t * _span(w, i, i + 1, axis)
                     for i, t in enumerate(shifted)])
    return taps if lead is None else lead + taps


def _shifts(x, before, k, axis):
    return [_behind(x, before, k - 1 - i, axis) for i in range(k)]


def _at(tokens, channels, axis):
    """The index of a window of a grid step's block."""
    return (0,) + _laid(tokens, channels, axis)


def _edge(t, axis, last):
    """The float32 tile at the start (``last``: the end) of ``t`` along
    the tokens' axis."""
    t, e = t.astype(_F32), _CONV_EDGE[axis]
    n = t.shape[axis]
    return _span(t, n - e, n, axis) if last else _span(t, 0, e, axis)


def _fold(v, axis):
    """``v`` summed along the tokens' axis down to ONE hardware tile of
    tokens: whole-register adds, no reduction inside a register."""
    e = _CONV_EDGE[axis]
    return _add_all([_span(v, i, i + e, axis)
                     for i in range(0, v.shape[axis], e)])


def _window(i, size):
    return pl.ds(pl.multiple_of(i * size, size), size)


def _channel_window(w_ref, bias_ref, j, size, axis):
    """Channel window ``j`` of a grid step: its slice and its float32
    taps and bias (None without one)."""
    ch = _window(j, size)
    lanes = _laid(slice(None), ch, axis)
    return ch, w_ref[lanes].astype(_F32), (
        None if bias_ref is None else bias_ref[lanes].astype(_F32))


def _conv_fwd_kernel(w_ref, *refs, biased, axis, sub):
    """A tile in windows of ``sub`` ``(tokens, channels)`` — a few
    registers, so that a window's ``pre`` and its sigmoid never leave them
    —, the tokens in order: a window's last tokens ride to the next."""
    x_ref, before_ref, y_ref = refs[biased:]
    k, (ts, cs) = w_ref.shape[axis], sub
    first = pl.program_id(2) == 0

    def channels(j, _):
        ch, w, lead = _channel_window(
            w_ref, refs[0] if biased else None, j, cs, axis)

        def tokens(i, before):
            at = _at(_window(i, ts), ch, axis)
            x = x_ref[at].astype(_F32)
            pre = _tile_pre(_shifts(x, before, k, axis), w, lead, axis)
            y_ref[at] = jax.nn.silu(pre).astype(y_ref.dtype)
            return _edge(x, axis, last=True)

        before = _edge(before_ref[_at(slice(None), ch, axis)],
                       axis, last=True)
        jax.lax.fori_loop(0, x_ref.shape[1 + axis] // ts, tokens,
                          jnp.where(first, 0.0, before))
        return 0

    jax.lax.fori_loop(0, x_ref.shape[2 - axis] // cs, channels, 0)


def _conv_bwd_kernel(w_ref, *refs, biased, axis, sub):
    """The windows of a tile against time: a window's first ``dpre`` ride
    to the one before it, as do the weight's and the bias's sums of the
    tile, one hardware tile of tokens each, until the tile's last window.
    ``dw_ref`` (and ``db_ref``) stay in VMEM over a channel block's tiles
    — the grid's two inner axes — and collect the tiles' sums in
    float32."""
    x_ref, before_ref, after_ref, dy_ref, dy_after_ref, dx_ref, dw_ref = (
        refs[biased:biased + 7])
    k, (ts, cs) = w_ref.shape[axis], sub
    halo = _CONV_HALO[axis]
    tile, tiles = pl.program_id(2), pl.num_programs(2)
    n = x_ref.shape[1 + axis]
    windows = n // ts

    @pl.when((pl.program_id(1) == 0) & (tile == 0))
    def _channel_blocks_first_tile():
        for ref in refs[biased + 6:]:
            ref[...] = jnp.zeros_like(ref)

    def channels(j, _):
        ch, w, lead = _channel_window(
            w_ref, refs[0] if biased else None, j, cs, axis)

        def pre_of(x, before):
            shifted = _shifts(x, before, k, axis)
            return shifted, _tile_pre(shifted, w, lead, axis)

        def halo_of(ref, start=None):
            tokens = slice(None) if start is None else pl.ds(start, halo)
            return ref[_at(tokens, ch, axis)]

        def window(ii, carry):
            dpre_after, sums = carry
            i = windows - 1 - ii
            at = _at(_window(i, ts), ch, axis)
            x, dy = x_ref[at].astype(_F32), dy_ref[at].astype(_F32)
            behind = halo_of(x_ref, pl.multiple_of(
                jnp.maximum(i * ts - halo, 0), halo))
            before = jnp.where(i == 0, tile_before,
                               _edge(behind, axis, last=True))
            shifted, pre = pre_of(x, before)
            dpre = _dsilu(pre, dy)
            dx_ref[at] = _add_all([
                _ahead(dpre, dpre_after, k - 1 - t, axis)
                * _span(w, t, t + 1, axis) for t in range(k)]
            ).astype(dx_ref.dtype)
            sums = tuple(acc + _fold(dpre if t is None else dpre * t, axis)
                         for acc, t in zip(sums, shifted + [None]))
            return _edge(dpre, axis, last=False), sums

        tile_before = jnp.where(tile == 0, 0.0,
                                _edge(halo_of(before_ref), axis, last=True))
        # the tokens after the tile: their ``pre`` reads the tile's last
        # ones, and past the sequence's end there is no gradient
        _, pre_after = pre_of(
            _edge(halo_of(after_ref), axis, last=False),
            _edge(halo_of(x_ref, n - halo), axis, last=True))
        dy_after = jnp.where(tile == tiles - 1, 0.0,
                             _edge(halo_of(dy_after_ref), axis, last=False))
        zero = jnp.zeros_like(dy_after)
        _, sums = jax.lax.fori_loop(
            0, windows, window,
            (_dsilu(pre_after, dy_after), (zero,) * (k + biased)))
        for t, acc in enumerate(sums):
            ref, tap = (dw_ref, t) if t < k else (refs[biased + 7], 0)
            at = _laid(slice(tap, tap + 1), ch, axis)
            ref[at] += jnp.sum(acc, axis=axis, keepdims=True)
        return 0

    jax.lax.fori_loop(0, x_ref.shape[2 - axis] // cs, channels, 0)


def _conv_plan(x, weight, bias, axis, tile, interpret, carried):
    """What the two calls share, ``x`` being ``(b, s, c)`` (``axis`` 0) or
    its transposed view ``(b, c, s)`` (1): the grid ``(channel block,
    batch row, token tile)``; the BlockSpecs of a tile, of the hardware
    tile of tokens before it and after it (clamped at the sequence's ends,
    where the kernels put zeros), of the weight's and the bias's channel
    block; the two leading operands, the channels along the axis they
    have in a tile.  ``carried``: the inner axes run in order (the
    backward's sums ride over them)."""
    t, c = tile
    tokens, channels = x.shape[1 + axis], x.shape[2 - axis]
    halo = _CONV_HALO[axis]
    halos, last = t // halo, tokens // halo - 1

    def spec(tokens_block, at):
        """A block ``tokens_block`` tokens long of the grid step's channel
        block, at the token block ``at(i)``."""
        block = _laid(tokens_block, c, axis)
        return pl.BlockSpec((1,) + block, lambda j, b, i: (
            (b,) + _laid(at(i), j, axis)))

    def lead(extent):  # the weight's taps or the bias, a channel block
        block = _laid(extent, c, axis)
        return pl.BlockSpec(block, lambda j, b, i: _laid(0, j, axis))

    operands = (weight,) if bias is None else (weight, bias[None])
    inner = "arbitrary" if carried else "parallel"
    return dict(
        grid=(channels // c, x.shape[0], tokens // t),
        tile=spec(t, lambda i: i),
        before=spec(halo, lambda i: jnp.maximum(i * halos - 1, 0)),
        after=spec(halo, lambda i: jnp.minimum((i + 1) * halos, last)),
        lead=[lead(weight.shape[0])] + ([] if bias is None else [lead(1)]),
        operands=operands if axis == 0 else tuple(t.T for t in operands),
        params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", inner, inner),
            vmem_limit_bytes=64 * 1024 * 1024))


_CONV_STATIC = ("axis", "tile", "sub", "interpret")


@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_fwd_call(x, weight, bias, *, axis, tile, sub, interpret):
    """``x (b, s, c)`` — ``axis`` 1: ``(b, c, s)`` —, ``weight (k, c)``,
    ``bias (c,)`` or None: the SiLU of the convolution, like ``x``."""
    sp = _conv_plan(x, weight, bias, axis, tile, interpret, carried=False)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, biased=bias is not None,
                          axis=axis, sub=sub),
        grid=sp["grid"],
        in_specs=sp["lead"] + [sp["tile"], sp["before"]],
        out_specs=sp["tile"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=sp["params"],
        interpret=interpret,
        name="causal_conv_fwd",
    )(*sp["operands"], x, x)


@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_bwd_call(x, weight, bias, dy, *, axis, tile, sub, interpret):
    """Gradients to ``x`` (like it), to ``weight`` ``(k, c)`` float32 and,
    where there is a bias, to it ``(c,)`` float32 (else None)."""
    sp = _conv_plan(x, weight, bias, axis, tile, interpret, carried=True)
    sums = [jax.ShapeDtypeStruct(t.shape, _F32) for t in sp["operands"]]
    dx, *sums = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, biased=bias is not None,
                          axis=axis, sub=sub),
        grid=sp["grid"],
        in_specs=sp["lead"] + [sp["tile"], sp["before"], sp["after"],
                               sp["tile"], sp["after"]],
        out_specs=[sp["tile"]] + sp["lead"],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] + sums,
        compiler_params=sp["params"],
        interpret=interpret,
        name="causal_conv_bwd",
    )(*sp["operands"], x, x, x, dy, dy)
    dw, *db = sums if axis == 0 else [t.T for t in sums]
    return dx, dw, db[0][0] if db else None


def _tokens_axis(tokens_last):
    """``(b, s, c)`` to the view the pair walks, and back."""
    return (lambda t: jnp.swapaxes(t, 1, 2)) if tokens_last else (lambda t: t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv_kernels(x, weight, bias, tokens_last=False):
    """``causal_conv1d`` through the Pallas pair (compiled on the TPU,
    interpreted elsewhere): the backward kernel makes a tile's ``pre``,
    sigmoid and ``dpre`` once in VMEM and sums the weight's and the bias's
    gradients in float32; nothing but the arguments is kept."""
    view = _tokens_axis(tokens_last)
    return view(_conv_fwd_call(view(x), weight, bias,
                               **_conv_static(x, tokens_last)))


def _conv_kernels_fwd(x, weight, bias, tokens_last):
    return conv_kernels(x, weight, bias, tokens_last), (x, weight, bias)


def _conv_kernels_bwd(tokens_last, res, dy):
    x, weight, bias = res
    view = _tokens_axis(tokens_last)
    dx, dw, db = _conv_bwd_call(view(x), weight, bias, view(dy),
                                **_conv_static(x, tokens_last))
    return (view(dx), dw.astype(weight.dtype),
            None if bias is None else db.astype(bias.dtype))


conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def _gate_split(bcx):
    """``[B | C | x]`` side by side -> the three, float32."""
    return tuple(t.astype(_F32) for t in jnp.split(bcx, 3, axis=-1))


@jax.custom_vjp
def gated_short_conv(bcx: jax.Array, weight: jax.Array) -> jax.Array:
    """The gated short convolution of LFM2's ``conv`` layers: with ``bcx
    (b, s, 3 d)`` holding ``[B | C | x]`` side by side and ``weight (k,
    d)``, ``y = C * conv(B * x)`` — the causal depthwise convolution of
    ``causal_conv1d`` (``weight[k-1]`` meets the current token, zeros
    before the sequence) with NO bias and NO activation, between two
    elementwise gates.  Float32 inside, ``bcx.dtype`` out ``(b, s, d)``.

    The backward pass is written out and keeps nothing but the arguments
    (``z = B * x`` and ``c = conv(z)`` are cheap to make again): ``dC = dy
    * c``, ``dc = dy * C``, ``dz`` the same convolution of ``dc`` run
    against time, ``dB = dz * x``, ``dx = dz * B``, ``dw_i = sum_t dc_t
    z_(t - (k-1) + i)``."""
    gate_in, gate_out, x = _gate_split(bcx)
    return (gate_out * _conv_pre(gate_in * x, weight, None)).astype(
        bcx.dtype)


def _gated_fwd(bcx, weight):
    return gated_short_conv(bcx, weight), (bcx, weight)


def _gated_bwd(res, dy):
    bcx, weight = res
    gate_in, gate_out, x = _gate_split(bcx)
    z = gate_in * x
    dy = dy.astype(_F32)
    dz, dw = _conv_grads(dy * gate_out, z, weight)
    d_bcx = jnp.concatenate(
        [dz * x, dy * _conv_pre(z, weight, None), dz * gate_in], axis=-1)
    return d_bcx.astype(bcx.dtype), dw.astype(weight.dtype)


gated_short_conv.defvjp(_gated_fwd, _gated_bwd)


def gated_rms_norm(y: jax.Array, z: jax.Array, weight: jax.Array,
                   eps: float, groups: int = 1) -> jax.Array:
    """Mamba-2's output norm: the GATE FIRST (``y * silu(z)``), then an
    RMSNorm over each of the ``groups`` equal parts of the last dimension
    on its own (one group: over the whole of it), under one weight of the
    whole width; float32 inside, ``y.dtype`` out.  (The other order, norm
    then gate, is Mamba-2's ``norm_before_gate``, which the published
    models do not use.)

    Several groups are ONE rule with its backward pass written out
    (``_grouped_norm``), which keeps nothing but its arguments and makes
    no array with an axis of groups where a group's channels fill whole
    lane tiles: a reshape of ``(b, s, inner)`` to ``(b, s, groups,
    width)`` puts the groups in the sublanes, and XLA then pays for the
    relayouts of every float32 intermediate, forward and backward."""
    if groups == 1:  # no reshape: the one-group program is what it was
        gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
        return rms_norm(gated, weight, eps).astype(y.dtype)
    return _grouped_norm(y, z, weight, eps, groups)


# ---------------------------------------------- the gated norm by groups
#
# Two forms of one rule, chosen by what the call's shapes show
# (``norm_kernels_fit``): where a group's channels fill whole lane tiles
# (Nemotron-H's 512), two Pallas kernels over tiles of the ``(tokens,
# inner)`` arrays as they stand, a grid step one group's lanes of a tile
# of rows — the statistics are lane reductions of a block and live in
# VMEM; elsewhere (the tests' tiny widths) the same sums as plain XLA on
# the reshaped arrays.  Both are ``_norm_rows`` / ``_norm_rows_bwd`` over
# a last axis that is ONE group's channels.

_NORM_ROWS = 1024     # token rows a grid step


def _gate_terms(y, z, eps):
    """What the norm's two passes both start from, float32, the last axis
    one group's channels: ``y``, ``z``, ``sigmoid(z)``, the gated value
    ``g = y * silu(z)`` and ``r = rsqrt(mean(g^2) + eps)``."""
    y, z = y.astype(_F32), z.astype(_F32)
    sig = jax.nn.sigmoid(z)
    g = y * (z * sig)
    r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return y, z, sig, g, r


def _norm_rows(y, z, w, eps):
    *_, g, r = _gate_terms(y, z, eps)
    return g * r * w.astype(_F32)


def _norm_rows_bwd(y, z, w, dout, eps):
    """The gradients to ``y`` and ``z`` and, a row, what it adds to the
    weight's: with ``n = g r`` the normed value and ``u = dout * w``, ``dg
    = r (u - n mean(n u))`` (the mean over the group, as ``r`` is its),
    ``dy = dg silu(z)``, ``dz = dg y silu'(z)``, ``dw = sum of dout n``."""
    y, z, sig, g, r = _gate_terms(y, z, eps)
    dout = dout.astype(_F32)
    n, u = g * r, dout * w.astype(_F32)
    dg = r * (u - n * jnp.mean(n * u, axis=-1, keepdims=True))
    return (dg * (z * sig), dg * y * (sig * (1.0 + z * (1.0 - sig))),
            dout * n)


def norm_kernels_fit(inner: int, groups: int) -> bool:
    """Whether a group's channels fill whole lane tiles: the kernels'
    blocks are cut at the groups."""
    return inner % groups == 0 and (inner // groups) % _LANES == 0


def _norm_fwd_kernel(y_ref, z_ref, w_ref, out_ref, *, eps):
    out_ref[...] = _norm_rows(y_ref[...], z_ref[...], w_ref[...], eps
                              ).astype(out_ref.dtype)


def _norm_bwd_kernel(y_ref, z_ref, w_ref, dout_ref, dy_ref, dz_ref, dw_ref,
                     *, eps, rows):
    """``dw_ref`` takes the tile's own sum (the tiles' are added outside);
    ``rows`` are the array's where its last tile overhangs it, else None:
    what lies beyond them is not data and stays out of the sum."""
    dy, dz, dw = _norm_rows_bwd(y_ref[...], z_ref[...], w_ref[...],
                                dout_ref[...], eps)
    dy_ref[...] = dy.astype(dy_ref.dtype)
    dz_ref[...] = dz.astype(dz_ref.dtype)
    if rows is not None:
        tile = dw.shape[0]
        inside = _iota((tile, 1), 0) < rows - pl.program_id(0) * tile
        dw = jnp.where(inside, dw, 0.0)
    dw_ref[0] = jnp.sum(dw, axis=0, keepdims=True)


def _norm_plan(y, groups, interpret):
    """What the two calls share: rows ``(tokens, inner)`` in tiles of
    ``_NORM_ROWS`` (the last may overhang), a grid step one group's lanes
    of a tile."""
    rows, inner = y.shape
    tile, width = min(_NORM_ROWS, rows), inner // groups
    return dict(
        grid=(pl.cdiv(rows, tile), groups),
        rows=pl.BlockSpec((tile, width), lambda i, j: (i, j)),
        w=pl.BlockSpec((1, width), lambda i, j: (0, j)),
        dw=pl.BlockSpec((1, 1, width), lambda i, j: (i, 0, j)),
        overhang=rows if rows % tile else None,
        params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024))


@functools.partial(jax.jit, static_argnames=("eps", "groups", "interpret"))
def _norm_fwd_call(y, z, w, *, eps, groups, interpret):
    """``y``, ``z`` ``(tokens, inner)``, ``w (1, inner)``: the normed
    gated value like ``y``."""
    sp = _norm_plan(y, groups, interpret)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        grid=sp["grid"],
        in_specs=[sp["rows"], sp["rows"], sp["w"]],
        out_specs=sp["rows"],
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=sp["params"],
        interpret=interpret,
        name="gated_norm_fwd",
    )(y, z, w)


@functools.partial(jax.jit, static_argnames=("eps", "groups", "interpret"))
def _norm_bwd_call(y, z, w, dout, *, eps, groups, interpret):
    """Gradients to ``y`` and ``z`` (each like its argument) and to ``w``
    a tile of rows ``(tiles, 1, inner)`` float32."""
    sp = _norm_plan(y, groups, interpret)
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps, rows=sp["overhang"]),
        grid=sp["grid"],
        in_specs=[sp["rows"], sp["rows"], sp["w"], sp["rows"]],
        out_specs=[sp["rows"], sp["rows"], sp["dw"]],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((sp["grid"][0], 1, y.shape[1]),
                                        _F32)],
        compiler_params=sp["params"],
        interpret=interpret,
        name="gated_norm_bwd",
    )(y, z, w, dout)


def _by_groups(t, groups):
    return t.reshape(*t.shape[:-1], groups, t.shape[-1] // groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_norm(y, z, weight, eps, groups):
    inner = y.shape[-1]
    if norm_kernels_fit(inner, groups):
        return _norm_fwd_call(
            y.reshape(-1, inner), z.reshape(-1, inner), weight[None],
            eps=eps, groups=groups,
            interpret=attention._interpret_default()).reshape(y.shape)
    return _norm_rows(*(_by_groups(t, groups) for t in (y, z, weight)),
                      eps).reshape(y.shape).astype(y.dtype)


def _grouped_norm_fwd(y, z, weight, eps, groups):
    return _grouped_norm(y, z, weight, eps, groups), (y, z, weight)


def _grouped_norm_bwd(eps, groups, res, dout):
    y, z, weight = res
    inner = y.shape[-1]
    if norm_kernels_fit(inner, groups):
        dy, dz, dw = _norm_bwd_call(
            *(t.reshape(-1, inner) for t in (y, z)), weight[None],
            dout.reshape(-1, inner), eps=eps, groups=groups,
            interpret=attention._interpret_default())
    else:
        dy, dz, dw = _norm_rows_bwd(
            *(_by_groups(t, groups) for t in (y, z, weight, dout)), eps)
    dw = jnp.sum(dw.reshape(-1, inner), axis=0)
    return (dy.reshape(y.shape).astype(y.dtype),
            dz.reshape(z.shape).astype(z.dtype), dw.astype(weight.dtype))


_grouped_norm.defvjp(_grouped_norm_fwd, _grouped_norm_bwd)


def exp_where(mask, x):
    """``exp(x)`` where ``mask`` and 0 elsewhere, with a gradient that is
    finite there too (the masked side may overflow)."""
    return jnp.exp(jnp.where(mask, x, -jnp.inf))


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, *, chunk: int) -> jax.Array:
    """The recurrence above for every head, in chunks of ``chunk`` tokens.

    ``x (batch, s, heads, head_dim)``; ``dt (batch, s, heads)`` float32,
    positive (after its softplus); ``a (heads,)`` float32, negative;
    ``b``, ``c`` ``(batch, s, groups, state)``, a group shared by
    ``heads / groups`` heads; ``d (heads,)``.  Returns ``y`` like ``x``.
    A sequence that is no multiple of the chunk is padded with tokens
    whose ``dt`` is 0 (no decay, no input), which the causal order keeps
    from every real output.

    The Pallas kernels where the shapes tile the chip (``kernels_fit``),
    the XLA form elsewhere: one algorithm, the same values to rounding."""
    form = ssd_kernels if kernels_fit(
        x.shape[2], x.shape[3], b.shape[2], b.shape[3],
        min(chunk, x.shape[1])) else ssd_xla
    return form(x, dt, a, b, c, d, chunk=chunk)


def kernels_fit(heads: int, head_dim: int, groups: int, state: int,
                chunk: int) -> bool:
    """Whether a call's shapes tile the chip for ``ssd_kernels``: whole
    head blocks of 128 lanes, each inside ONE group (a block's heads share
    the B and C that its grid step is handed), a state and a chunk that
    are multiples of the lane width (both are a matmul's minor
    dimension)."""
    block = _LANES // head_dim if head_dim and _LANES % head_dim == 0 else 0
    return (1 <= block <= _MAX_BLOCK_HEADS and heads % groups == 0
            and (heads // groups) % block == 0 and state % _LANES == 0
            and chunk % _LANES == 0)


def pad_to_multiple(q, *tensors):
    """``tensors`` ``(batch, s, ...)`` padded with zeros along ``s`` to a
    multiple of ``q``."""
    pad = -tensors[0].shape[1] % q
    if not pad:
        return tensors
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in tensors)


def ssd_xla(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
            c: jax.Array, d: jax.Array, *, chunk: int) -> jax.Array:
    """``ssd_chunked`` as plain XLA, for any shapes: the chunk's matrices
    are arrays in memory and autodiff writes the backward pass."""
    batch, s, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    q = min(chunk, s)
    x, dt, b, c = pad_to_multiple(q, x, dt, b, c)
    nc = x.shape[1] // q
    dtype = x.dtype
    dt = dt.astype(_F32)
    xg = x.reshape(batch, nc, q, groups, r, p)
    dtg = dt.reshape(batch, nc, q, groups, r)
    b = b.reshape(batch, nc, q, groups, n)
    c = c.reshape(batch, nc, q, groups, n)

    # log-decay from the chunk's start to each token, token included
    acs = jnp.cumsum(dtg * a.astype(_F32).reshape(groups, r), axis=2)
    acs_t = jnp.moveaxis(acs, 2, -1)                      # (B, C, g, r, q)
    xdt = (xg.astype(_F32) * dtg[..., None]).astype(dtype)

    # inside a chunk: (C B^T o L) (dt x)
    cb = jnp.einsum("zcqgn,zckgn->zcgqk", c, b, preferred_element_type=_F32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = exp_where(causal, acs_t[..., :, None] - acs_t[..., None, :])
    m = (cb[:, :, :, None] * decay).astype(dtype)         # (B, C, g, r, q, k)
    y = jnp.einsum("zcgrqk,zckgrp->zcqgrp", m, xdt,
                   preferred_element_type=_F32)

    # what each chunk leaves behind: B^T (decay to the chunk's end o dt x)
    to_end = jnp.exp(acs[:, :, -1:] - acs)                # (B, C, q, g, r)
    left = jnp.einsum(
        "zckgn,zckgrp->zcgrpn", b,
        (xdt.astype(_F32) * to_end[..., None]).astype(dtype),
        preferred_element_type=_F32)

    # across chunks, float32: the state entering chunk i is the sum over
    # earlier chunks j of (decay from the end of j to the start of i) x left_j
    total = jnp.moveaxis(acs[:, :, -1], 1, -1)            # (B, g, r, C)
    upto = jnp.cumsum(total, axis=-1)
    start = upto - total                                  # log-decay before i
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)
    carry = exp_where(earlier, start[..., :, None] - upto[..., None, :])
    entering = jnp.einsum("zgrij,zjgrpn->zigrpn", carry, left,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + jnp.einsum("zcqgn,zcgrpn->zcqgrp", c, entering.astype(dtype),
                       preferred_element_type=_F32) * jnp.exp(acs)[..., None]

    y = y + xg.astype(_F32) * d.astype(_F32).reshape(groups, r)[..., None]
    return y.astype(dtype).reshape(batch, nc * q, heads, p)[:, :s]


# ------------------------------------------------------- the Pallas form
#
# A grid step takes one chunk of a few head blocks of one group in turn, a
# block being ``hb`` heads whose ``hb * p`` values a token fill the lanes
# of a ``(q, w)`` tile of x.  A head's scalars a token (dt, its cumulative
# log-decay) come as columns ``(q, 1)`` picked from the ``(q, heads)``
# block and as rows ``(1, q)`` cut from its transpose; ``_spread`` lays a
# value a head over that head's lanes, ``_only`` blanks the other heads'
# lanes so that one 128-wide product serves a head of 64 at the cost the
# MXU charges for 64 anyway.


def _dot(a, b, contract):
    """``a`` x ``b`` contracting axis ``contract[0]`` of ``a`` with axis
    ``contract[1]`` of ``b``, float32 out."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _spread(values, shape, axis, p):
    """``shape`` filled along ``axis`` with ``values[i]`` where head ``i``
    of the block sits (``p`` positions each)."""
    out = jnp.broadcast_to(values[-1], shape)
    for i in range(len(values) - 2, -1, -1):
        out = jnp.where(_iota(shape, axis) < (i + 1) * p, values[i], out)
    return out


def _only(v, i, hb, p, axis=1):
    """``v`` with the positions of every head but ``i`` zeroed."""
    if hb == 1:
        return v
    at = _iota(v.shape, axis)
    return jnp.where((at >= i * p) & (at < (i + 1) * p), v,
                     jnp.zeros_like(v))


def _per_head(v, hb, p, axis):
    """The sums of ``v`` over each head's positions along ``axis``."""
    return [jnp.sum(_only(v, i, hb, p, axis), axis=axis, keepdims=True)
            for i in range(hb)]


def _causal(cb, transposed=False):
    """``cb (q, q)`` with zeros above the diagonal (below it, for the
    transposed matrix)."""
    rows, cols = _iota(cb.shape, 0), _iota(cb.shape, 1)
    return jnp.where(rows <= cols if transposed else rows >= cols, cb, 0.0)


def _chunk_terms(x, dt_ref, acs_ref, acsr_ref, cb, k, hb, p,
                 transposed=False):
    """What forward and backward both make of one chunk of head block
    ``k`` from ``cb``, the causal part of ``C B^T``: per head the masked
    matrix ``M = (C B^T o L)`` in ``x.dtype`` and ``L`` in float32, which
    is right on and below the diagonal only (above it the decays are
    capped at 1 and ``cb`` is 0, so the step masks once, not once a
    head) — or, ``transposed``, ``M^T`` and ``L^T`` from ``cb^T``, made
    in place: a transpose of ``M`` a head is a third of the backward
    kernel's time; over the block's lanes ``dt``, ``dt x``, ``exp(acs)``
    (decay from the chunk's start), ``dt x`` decayed to the chunk's end;
    down the state's rows the chunk's whole decay."""
    q, w = x.shape
    dtype = x.dtype
    heads = [k * hb + i for i in range(hb)]
    dt_all, acs_all = dt_ref[0], acs_ref[0]               # (q, heads)
    lane = _iota(dt_all.shape, 1)

    def column(v, h):
        return jnp.sum(jnp.where(lane == h, v, 0.0), axis=1, keepdims=True)

    dts = [column(dt_all, h) for h in heads]              # (q, 1) each
    cols = [column(acs_all, h) for h in heads]
    rows = [acsr_ref[0, pl.ds(h, 1), :] for h in heads]   # (1, q) each
    at_end = _iota((1, q), 1) == q - 1
    lasts = [jnp.sum(jnp.where(at_end, r, 0.0), axis=1, keepdims=True)
             for r in rows]                               # (1, 1) each
    ls = [jnp.exp(jnp.minimum(row - col if transposed else col - row, 0.0))
          for col, row in zip(cols, rows)]
    dt_l = _spread(dts, (q, w), 1, p)
    acs_l = _spread(cols, (q, w), 1, p)
    xd = (x.astype(_F32) * dt_l).astype(dtype)
    to_end = jnp.exp(_spread(lasts, (q, w), 1, p) - acs_l)
    ends = [jnp.exp(v) for v in lasts]                    # the chunk's decay
    return dict(
        heads=heads, dts=dts, ls=ls, ms=[(cb * l).astype(dtype) for l in ls],
        dt_l=dt_l, xd=xd, from_start=jnp.exp(acs_l), to_end=to_end,
        xd_end=(xd.astype(_F32) * to_end).astype(dtype),
        ends=ends, decay=_spread(ends, (w, 1), 0, p))


def _each_block(nb, w, trip, body):
    """``body(g, lanes)`` for each of a grid step's ``nb`` head blocks,
    ``lanes`` the block's ``w`` of the step's ``nb * w``: a loop of
    ``trip`` blocks a trip, not ``nb`` copies of the body."""
    def blocks(t, carry):
        for i in range(trip):
            g = t * trip + i
            body(g, pl.ds(pl.multiple_of(g * w, w), w))
        return carry

    jax.lax.fori_loop(0, nb // trip, blocks, 0)


def _fwd_kernel(x_ref, dt_ref, acs_ref, acsr_ref, b_ref, c_ref, d_ref,
                y_ref, states_ref, h_scr, *, hb, p, nb, trip):
    j, first_chunk = pl.program_id(2), pl.program_id(1) == 0
    bm, cm = b_ref[0], c_ref[0]
    cb = _causal(_dot(cm, bm, (1, 1)))                    # (q, q)

    def block(g, lanes):
        k = j * nb + g
        x = x_ref[0, :, lanes]
        t = _chunk_terms(x, dt_ref, acs_ref, acsr_ref, cb, k, hb, p)
        h = jnp.where(first_chunk, 0.0, h_scr[k])         # (w, n) float32
        y = (t["from_start"] * _dot(cm, h.astype(x.dtype), (1, 1))
             + x.astype(_F32) * d_ref[:, lanes])
        for i, m in enumerate(t["ms"]):
            y += _dot(m, _only(t["xd"], i, hb, p), (1, 0))
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        states_ref[0, 0, lanes, :] = h
        h_scr[k] = t["decay"] * h + _dot(t["xd_end"], bm, (0, 0))

    _each_block(nb, hb * p, trip, block)


def _bwd_kernel(x_ref, dt_ref, acs_ref, acsr_ref, b_ref, c_ref, d_ref,
                states_ref, dy_ref,
                dx_ref, ddt_ref, dacs_ref, dacsr_ref, db_ref, dc_ref, dd_ref,
                dh_scr, db_scr, dc_scr, dcbt_scr, *, hb, p, nb, trip,
                group_steps):
    """Chunks from the last to the first (the index maps turn the chunk
    axis round); ``dh_scr[k]`` is the gradient to the state LEAVING the
    chunk.  ``db_scr`` and ``dc_scr`` collect a GROUP's gradients to B
    and C over its ``group_steps`` grid steps, the sums over its heads.
    Every ``(q, q)`` matrix here is the TRANSPOSE of the forward kernel's
    (``[j, i]``: token ``j`` feeds token ``i >= j``).  The gradient to a
    head's cumulative log-decays comes in two parts that
    are added outside: down the tokens (``dacs_ref``) what each token's
    own decays collect less the row sums of ``dM^T o M^T``, and along
    them (``dacsr_ref``) its column sums — sums of ONE float32 matrix,
    because their difference is summed again over the chunk and does not
    survive two roundings."""
    j = pl.program_id(2)
    group_step = j % group_steps
    last_chunk = pl.program_id(1) == 0
    bm, cm = b_ref[0], c_ref[0]
    q, dtype = bm.shape[0], x_ref.dtype
    cbt = _causal(_dot(bm, cm, (1, 1)), transposed=True)
    dcbt_scr[...] = jnp.zeros_like(dcbt_scr)

    @pl.when(group_step == 0)
    def _group_first_step():
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    @pl.when(j == 0)
    def _first_step():
        ddt_ref[...] = jnp.zeros_like(ddt_ref)
        dacs_ref[...] = jnp.zeros_like(dacs_ref)

    def block(g, lanes):
        k = j * nb + g
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        t = _chunk_terms(x, dt_ref, acs_ref, acsr_ref, cbt, k, hb, p,
                         transposed=True)
        xf, dyf = x.astype(_F32), dy.astype(_F32)
        h = states_ref[0, 0, lanes, :]                    # entering, (w, n)
        dh = jnp.where(last_chunk, 0.0, dh_scr[k])
        h_lo, dh_lo = h.astype(dtype), dh.astype(dtype)

        y_in = t["from_start"] * _dot(cm, h_lo, (1, 1))   # from the state
        dxd_end = t["to_end"] * _dot(bm, dh_lo, (1, 1))   # through the state
        dxd, fed = dxd_end, []
        for i, (mt, lt) in enumerate(zip(t["ms"], t["ls"])):
            dy_i = _only(dy, i, hb, p)
            dxd += _dot(mt, dy_i, (1, 0))
            dlt = _dot(t["xd"], dy_i, (1, 1)) * lt        # (dM o L)^T
            dcbt_scr[...] += dlt
            dmmt = dlt * cbt                              # (dM o M)^T
            fed.append(jnp.sum(dmmt, axis=1, keepdims=True))
            dacsr_ref[0, pl.ds(t["heads"][i], 1), :] = jnp.sum(
                dmmt, axis=0, keepdims=True)
        dy_start = (t["from_start"] * dyf).astype(dtype)
        dc_scr[...] += _dot(dy_start, h_lo, (1, 0))
        db_scr[...] += _dot(t["xd_end"], dh_lo, (1, 0))
        dh_scr[k] = t["decay"] * dh + _dot(dy_start, cm, (0, 0))

        dx_ref[0, :, lanes] = (t["dt_l"] * dxd + d_ref[:, lanes] * dyf
                               ).astype(dx_ref.dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        at_last = _iota((q, 1), 0) == q - 1
        lane = _iota(ddt_ref.shape[1:], 1)
        ddt = jnp.zeros(ddt_ref.shape[1:], _F32)
        dacs = jnp.zeros(dacs_ref.shape[1:], _F32)
        for i, (ddt_i, start_i, end_i, carry_i) in enumerate(zip(
                _per_head(dxd * xf, hb, p, 1),
                _per_head(dyf * y_in, hb, p, 1),
                _per_head(dxd_end * t["xd"].astype(_F32), hb, p, 1),
                _per_head(jnp.sum(dh * h, axis=1, keepdims=True),
                          hb, p, 0))):
            at_end = (jnp.sum(end_i, axis=0, keepdims=True)
                      + t["ends"][i] * carry_i)
            dacs_i = (start_i - end_i - fed[i]
                      + jnp.where(at_last, at_end, 0.0))
            ddt = jnp.where(lane == t["heads"][i], ddt_i, ddt)
            dacs = jnp.where(lane == t["heads"][i], dacs_i, dacs)
        ddt_ref[0] += ddt
        dacs_ref[0] += dacs

    _each_block(nb, hb * p, trip, block)
    # of the step's heads; dcb = dcbt^T
    dcbt = _causal(dcbt_scr[...], transposed=True).astype(dtype)
    dc_scr[...] += _dot(dcbt, bm, (0, 0))
    db_scr[...] += _dot(dcbt, cm, (1, 0))

    @pl.when(group_step == group_steps - 1)
    def _group_last_step():
        db_ref[0] = db_scr[...].astype(db_ref.dtype)
        dc_ref[0] = dc_scr[...].astype(dc_ref.dtype)


def _compiler_params(interpret):
    if interpret:
        return None
    # Chunks carry the state and a group's head blocks share the chunk's B
    # and C gradients: both axes run in order.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _plan(x, dt, bm, q, p, groups, reverse):
    """What the two calls share: the grid ``(batch, chunk, step of the
    chunk)``; the kernels' blocking — a block is the heads that fill the
    lanes, a step as many blocks as ``_STEP_BLOCKS`` allows and ONE
    GROUP's blocks divide into, so that a step's heads share one B and C,
    a group being ``group_steps`` steps in a row, ``trip`` blocks a trip
    of the step's loop —; the BlockSpecs, a grid
    step working on chunk ``c`` or, ``reverse``, on the chunk as far from
    the end, B and C ``(b, s, groups * n)`` cut at the step's group; the
    VMEM scratch that carries one ``(w, n)`` state a head block."""
    batch, s, _ = x.shape
    heads, n = dt.shape[2], bm.shape[2] // groups
    hb = min(heads, max(1, _LANES // p), _MAX_BLOCK_HEADS)
    if heads % groups or (heads // groups) % hb:
        raise ValueError(f"a block of {hb} heads straddles the {groups} "
                         f"groups of {heads} heads")
    nb = _STEP_BLOCKS
    while (heads // groups // hb) % nb:
        nb //= 2
    trip = min(nb, max(1, _TRIP_ELEMENTS // (q * q)))
    nc, w = s // q, nb * hb * p
    group_steps = heads // groups // hb // nb

    def spec(block, index):
        return pl.BlockSpec(block, lambda b_, c_, j_: index(
            b_, nc - 1 - c_ if reverse else c_, j_))

    return dict(
        grid=(batch, nc, groups * group_steps),
        blocking=dict(hb=hb, p=p, nb=nb, trip=trip), group_steps=group_steps,
        carry=pltpu.VMEM((heads // hb, hb * p, n), _F32),
        x=spec((1, q, w), lambda b_, c_, j_: (b_, c_, j_)),
        col=spec((1, q, heads), lambda b_, c_, j_: (b_, c_, 0)),
        row=spec((1, heads, q), lambda b_, c_, j_: (b_, 0, c_)),
        bc=spec((1, q, n), lambda b_, c_, j_: (b_, c_, j_ // group_steps)),
        d=spec((1, w), lambda b_, c_, j_: (0, j_)),
        states=spec((1, 1, w, n), lambda b_, c_, j_: (b_, c_, j_, 0)),
        dd=spec((1, 1, 1, w), lambda b_, c_, j_: (b_, c_, 0, j_)))


class _Static(NamedTuple):
    """What the two calls are compiled for, beside their shapes."""
    q: int            # the chunk
    p: int            # the head size
    groups: int
    interpret: bool


@functools.partial(jax.jit, static_argnames=_Static._fields)
def _fwd_call(x, dt, acs, bm, cm, d_l, *, q, p, groups, interpret):
    """``x (b, s, heads * p)``, ``dt`` and ``acs`` (its cumulative
    log-decay inside each chunk of ``q``) ``(b, s, heads)`` float32,
    ``bm``, ``cm`` ``(b, s, groups * n)``, ``d_l (1, heads * p)`` float32.
    Returns ``y`` like ``x`` and the state entering every chunk ``(b, s /
    q, heads * p, n)`` float32."""
    sp = _plan(x, dt, bm, q, p, groups, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **sp["blocking"]),
        grid=sp["grid"],
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                  sp["bc"], sp["d"]],
        out_specs=[sp["x"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (x.shape[0], x.shape[1] // q, x.shape[2],
                        bm.shape[2] // groups), _F32)],
        scratch_shapes=[sp["carry"]],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="ssd_fwd",
    )(x, dt, acs, jnp.swapaxes(acs, 1, 2), bm, cm, d_l)


@functools.partial(jax.jit, static_argnames=_Static._fields)
def _bwd_call(x, dt, acs, bm, cm, d_l, states, dy, *, q, p, groups,
              interpret):
    """Gradients to ``x``, ``dt``, ``acs`` (two parts: like ``acs`` and
    like its transpose), ``bm``, ``cm`` (each like its argument: a group's
    is the sum over its heads) and to ``d_l`` a chunk ``(b, s / q, 1,
    heads * p)``."""
    sp = _plan(x, dt, bm, q, p, groups, reverse=True)
    n = bm.shape[2] // groups
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **sp["blocking"],
                          group_steps=sp["group_steps"]),
        grid=sp["grid"],
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                  sp["bc"], sp["d"], sp["states"], sp["x"]],
        out_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                   sp["bc"], sp["dd"]],
        out_shape=[like(x), like(dt), like(acs),
                   jax.ShapeDtypeStruct(
                       (acs.shape[0], acs.shape[2], acs.shape[1]), _F32),
                   like(bm), like(cm),
                   jax.ShapeDtypeStruct(
                       (x.shape[0], x.shape[1] // q, 1, x.shape[2]), _F32)],
        scratch_shapes=[sp["carry"], pltpu.VMEM((q, n), _F32),
                        pltpu.VMEM((q, n), _F32), pltpu.VMEM((q, q), _F32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="ssd_bwd",
    )(x, dt, acs, jnp.swapaxes(acs, 1, 2), bm, cm, d_l, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, acs, bm, cm, d_l, static: _Static):
    return _fwd_call(x, dt, acs, bm, cm, d_l, **static._asdict())[0]


def _scan_fwd(x, dt, acs, bm, cm, d_l, static):
    y, states = _fwd_call(x, dt, acs, bm, cm, d_l, **static._asdict())
    return y, (x, dt, acs, bm, cm, d_l, states)


def _scan_bwd(static, res, dy):
    dx, ddt, dacs, dacs_t, db, dc, dd = _bwd_call(*res, dy,
                                                  **static._asdict())
    return (dx, ddt, dacs + jnp.swapaxes(dacs_t, 1, 2), db, dc,
            jnp.sum(dd, axis=(0, 1)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_kernels(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, d: jax.Array, *, chunk: int) -> jax.Array:
    """``ssd_chunked`` through the Pallas kernels (compiled on the TPU,
    interpreted elsewhere).  XLA pads, makes each chunk's cumulative
    log-decays and lays ``D`` over its heads' lanes, and differentiates
    those; the rest is ``ssd_fwd`` and ``ssd_bwd``, which take B and C
    with their groups side by side, ``(b, s, groups * state)``: the
    layout the convolution's split has, nothing repeated to the heads."""
    batch, s, heads, p = x.shape
    q = min(chunk, s)
    x, dt, b, c = pad_to_multiple(q, x, dt.astype(_F32), b, c)
    padded = x.shape[1]
    acs = jnp.cumsum((dt * a.astype(_F32)).reshape(batch, padded // q, q,
                                                   heads), axis=2)
    y = _scan(x.reshape(batch, padded, heads * p), dt,
              acs.reshape(batch, padded, heads),
              b.reshape(batch, padded, -1), c.reshape(batch, padded, -1),
              jnp.repeat(d.astype(_F32), p)[None],
              _Static(q, p, b.shape[2], attention._interpret_default()))
    return y.reshape(batch, padded, heads, p)[:, :s]


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d: jax.Array) -> jax.Array:
    """The recurrence one token at a time, float32 throughout (arguments
    as ``ssd_chunked``'s): ``lax.scan`` over positions carrying ``H``
    ``(batch, heads, head_dim, state)``.  For tests."""
    batch, _, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))
    a, d = a.astype(_F32), d.astype(_F32)

    def token(h, inputs):
        x_t, dt_t, b_t, c_t = inputs       # (B, h, p), (B, h), (B, g, n) x 2
        b_t, c_t = (jnp.repeat(t, r, axis=1) for t in (b_t, c_t))
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("zhpn,zhn->zhp", h, c_t,
                         precision=jax.lax.Precision.HIGHEST) + d[:, None] * x_t
        return h, y_t

    _, y = jax.lax.scan(
        token, jnp.zeros((batch, heads, p, n), _F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)



# ---- the selective scan (Mamba-1, "S6": Gu & Dao 2023, arXiv:2312.00752) ----
# A state of ``n`` numbers a CHANNEL, whose decay differs by channel, state
# index and token:
#
#     H_t[c, n] = exp(dt_t[c] A[c, n]) H_(t-1)[c, n] + dt_t[c] B_t[n] x_t[c]
#     m_t[c]    = sum_n C_t[n] H_t[c, n] + D[c] x_t[c]
#
# No chunk of it is a matrix product (``ssd_chunked`` rests on ONE decay a
# head): it is an elementwise recurrence, work for the vector unit, and the
# ``(tokens, channels, n)`` array of its states is never written.  Two forms
# of the one rule, by what a call's shapes show (``selscan_kernels_fit``):
#
# - ``selscan_kernels``: the Pallas pair ``selscan_fwd`` / ``selscan_bwd``,
#   TOKEN-SERIAL.  x, dt and m are turned to ``(b, s, c / 128, 128)``, so
#   that ONE token's 1024 channels are one ``(8, 128)`` vector register, and
#   a grid step ``(batch, chunk of _SEL_CHUNK tokens, block of 1024
#   channels)`` walks its chunk a token at a time with the block's ``n``
#   state registers as the loop's carry; whatever is indexed by ``n`` alone
#   — ``B_t[n]``, ``C_t[n]`` — is a SCALAR read from SMEM and splat, so no
#   lane is ever moved.  The forward writes the state LEAVING each chunk
#   (float32, ``s / 128 x n x c``: 42 MB a layer at 16384 x 5120 x 16), all
#   the backward needs beside the inputs: it walks the chunks in reverse,
#   makes a chunk's states again into VMEM, and runs the adjoint recurrence
#   back through them.  The gradients to B and C are sums over CHANNELS, a
#   scalar a (token, n): the step adds each product into a VMEM array over
#   the channel blocks (the grid's innermost axis) and at the last block
#   folds the 8 sublanes and writes ``(tokens, n, 128)`` partial sums, which
#   XLA finishes.
# - ``selscan_xla``: chunks of ``chunk`` tokens under ``lax.scan``, inside a
#   chunk an associative scan over ``(decay, input)`` pairs — products of
#   numbers in (0, 1], so no exponent ever grows — each chunk under a
#   checkpoint, so that a pass holds ``(chunk, n, channels)`` and the
#   backward keeps one state a chunk.  For any shapes; the tests' second
#   oracle beside ``selscan_reference``, the recurrence itself.
#
# Both return ``(m, the largest |H| at a chunk's end)``, the second a step
# statistic without a gradient.

from jax.ad_checkpoint import checkpoint_name  # noqa: E402 (the lines above keep their numbers: the kernels' bodies embed them)

_SEL_CHUNK = 128                 # tokens a grid step of the kernels walks
_SEL_BLOCK = 8 * _LANES          # channels of a block: one float32 register


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array, *, chunk: int = 64):
    """The recurrence above.  ``x (batch, s, channels)``; ``dt`` like ``x``,
    float32, positive (after its softplus); ``a (channels, n)`` float32,
    negative; ``b``, ``c`` ``(batch, s, n)``; ``d (channels,)``.  Returns
    ``m`` like ``x`` and the largest ``|H|`` at a chunk's end (float32, no
    gradient).  ``H`` starts at 0, is float32 throughout and exact: no
    state is truncated."""
    if selscan_kernels_fit(x.shape[2]):
        return selscan_kernels(x, dt, a, b, c, d)
    return selscan_xla(x, dt, a, b, c, d, chunk=chunk)


def selscan_kernels_fit(channels: int) -> bool:
    """Whether a call's channels fill whole ``(8, 128)`` registers."""
    return channels % _SEL_BLOCK == 0


def selscan_reference(x, dt, a, b, c, d):
    """The recurrence one token at a time, float32 (arguments as
    ``selective_scan``'s): ``lax.scan`` over positions carrying ``H (batch,
    channels, n)``.  Returns ``(m, the largest |H| of any token)``."""
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))
    a, d = a.astype(_F32), d.astype(_F32)

    def token(carry, at):
        h, peak = carry
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        m_t = jnp.sum(h * c_t[:, None, :], -1) + d * x_t
        return (h, jnp.maximum(peak, jnp.max(jnp.abs(h)))), m_t

    zero = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), _F32)
    (_, peak), m = jax.lax.scan(
        token, (zero, jnp.zeros((), _F32)),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(m, 0, 1), peak


def selscan_xla(x, dt, a, b, c, d, *, chunk: int = 64):
    """``selective_scan`` as plain XLA, differentiated by autodiff."""
    batch, s, channels = x.shape
    q = min(chunk, s)
    xs, dts, bs, cs = pad_to_multiple(q, x, dt.astype(_F32), b, c)
    a_t, d32 = a.astype(_F32).T, d.astype(_F32)     # (n, channels)

    def by_chunk(t):
        return jnp.moveaxis(t.reshape(batch, -1, q, t.shape[-1]), 1, 0)

    def combine(early, late):
        return early[0] * late[0], late[0] * early[1] + late[1]

    @jax.checkpoint
    def one_chunk(h, at):
        x_c, dt_c, b_c, c_c = (t.astype(_F32) for t in at)
        decay = jnp.exp(dt_c[:, :, None, :] * a_t)
        fed = (dt_c * x_c)[:, :, None, :] * b_c[..., None]
        kept, added = jax.lax.associative_scan(combine, (decay, fed), axis=1)
        states = kept * h[:, None] + added          # (batch, q, n, channels)
        m = jnp.sum(states * c_c[..., None], axis=2) + d32 * x_c
        return states[:, -1], (m.astype(x.dtype), jnp.max(jnp.abs(
            jax.lax.stop_gradient(states[:, -1]))))

    _, (m, peaks) = jax.lax.scan(
        one_chunk, jnp.zeros((batch, a.shape[1], channels), _F32),
        tuple(map(by_chunk, (xs, dts, bs, cs))))
    m = jnp.moveaxis(m, 0, 1).reshape(batch, -1, channels)[:, :s]
    return m, jnp.max(peaks)


def _sel_state(a_ref, b_ref, t, i, n, dt_t, fed, h):
    """State index ``i`` of a block after token ``t``: ``exp(dt A) H + B dt
    x`` (``fed`` = ``dt x``; ``B_t[i]`` a scalar out of SMEM)."""
    return jnp.exp(dt_t * a_ref[i]) * h + b_ref[0, t * n + i] * fed


def _sel_fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref,
                    m_ref, states_ref, carry, *, n, q):
    """One chunk of one channel block, a token at a time.  ``b_ref``,
    ``c_ref``: the chunk's ``(1, q x n)`` scalars in SMEM; ``x_ref``,
    ``dt_ref``, ``m_ref`` ``(q, 8, 128)``; ``a_ref (n, 8, 128)``; ``d_ref
    (8, 128)``; ``states_ref (n, 8, 128)``: the state LEAVING the chunk;
    ``carry (blocks, n, 8, 128)``: every block's state from chunk to
    chunk."""
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _start():
        carry[j] = jnp.zeros(carry.shape[1:], _F32)

    entering = carry[j]
    d = d_ref[...]

    def token(t, hs):
        x_t, dt_t = x_ref[t], dt_ref[t]
        fed, m_t, out = dt_t * x_t, d * x_t, []
        for i in range(n):
            h = _sel_state(a_ref, b_ref, t, i, n, dt_t, fed, hs[i])
            m_t = m_t + c_ref[0, t * n + i] * h
            out.append(h)
        m_ref[t] = m_t
        return tuple(out)

    hs = jax.lax.fori_loop(0, q, token, tuple(entering[i] for i in range(n)))
    for i in range(n):
        carry[j, i] = hs[i]
        states_ref[i] = hs[i]


def _sel_bwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, states_ref,
                    dm_ref, dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                    carry, hist, pb, pc, *, n, q, blocks, chunks):
    """The adjoint of ``_sel_fwd_kernel``'s chunk, the chunks in reverse
    (``states_ref``: what the chunk BEFORE this one left; the first chunk
    starts from 0):
    the chunk's states made again into ``hist (q + 1, n, 8, 128)`` (its
    first entry the state that entered), then back through the tokens with
    ``G[n] = dA_(t+1) dH_(t+1)`` as the loop's carry (``carry``: every
    block's, from chunk to chunk).  ``da_ref (n, 8, 128)`` and ``dd_ref (8,
    128)`` are the chunk's own sums (XLA adds the chunks'); ``db_ref``,
    ``dc_ref`` ``(q, n, 128)`` the chunk's ``dH dt x`` and ``dm H`` summed
    over the channel blocks (``pb``, ``pc``: ``(q, n, 8, 128)``) and the 8
    sublanes, written at the last block."""
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _start():
        carry[j] = jnp.zeros(carry.shape[1:], _F32)

    @pl.when(j == 0)
    def _first_block():
        pb[...] = jnp.zeros(pb.shape, _F32)
        pc[...] = jnp.zeros(pc.shape, _F32)

    entering = jnp.where(k == chunks - 1, 0.0, states_ref[...])
    hist[0] = entering

    def forward(t, hs):
        x_t, dt_t = x_ref[t], dt_ref[t]
        fed, out = dt_t * x_t, []
        for i in range(n):
            h = _sel_state(a_ref, b_ref, t, i, n, dt_t, fed, hs[i])
            hist[t + 1, i] = h
            out.append(h)
        return tuple(out)

    jax.lax.fori_loop(0, q, forward, tuple(entering[i] for i in range(n)))
    d, zero = d_ref[...], jnp.zeros((8, _LANES), _F32)

    def backward(step, carried):
        gs, das, dd = carried
        t = q - 1 - step
        x_t, dt_t, dm_t = x_ref[t], dt_ref[t], dm_ref[t]
        fed, dfed, ddt_t, new_gs, new_das = dt_t * x_t, zero, zero, [], []
        for i in range(n):
            a_i = a_ref[i]
            decay = jnp.exp(dt_t * a_i)
            dh = gs[i] + c_ref[0, t * n + i] * dm_t
            pc[t, i] += dm_t * hist[t + 1, i]
            pb[t, i] += dh * fed
            dfed = dfed + b_ref[0, t * n + i] * dh
            grown = dh * hist[t, i] * decay     # to the exponent dt A
            ddt_t = ddt_t + grown * a_i
            new_das.append(das[i] + grown * dt_t)
            new_gs.append(dh * decay)
        dx_ref[t] = dfed * dt_t + d * dm_t
        ddt_ref[t] = ddt_t + dfed * x_t
        return tuple(new_gs), tuple(new_das), dd + dm_t * x_t

    coming = carry[j]
    gs, das, dd = jax.lax.fori_loop(
        0, q, backward, (tuple(coming[i] for i in range(n)),
                         (zero,) * n, zero))
    for i in range(n):
        carry[j, i] = gs[i]
        da_ref[i] = das[i]
    dd_ref[...] = dd

    @pl.when(j == blocks - 1)
    def _last_block():
        def fold(t, _):
            for i in range(n):
                db_ref[t, pl.ds(i, 1), :] = jnp.sum(pb[t, i], axis=0,
                                                    keepdims=True)
                dc_ref[t, pl.ds(i, 1), :] = jnp.sum(pc[t, i], axis=0,
                                                    keepdims=True)
            return 0

        jax.lax.fori_loop(0, q, fold, 0)


def _sel_plan(batch, s, channels, n, reverse):
    """What the two calls share: the grid ``(batch, chunk, channel
    block)`` — the chunks in reverse for the backward —, the BlockSpecs
    over ``(b, s, c / 128, 128)`` arrays, the scalars' SMEM blocks and the
    scratch that carries a state a block."""
    q, rows = _SEL_CHUNK, _SEL_BLOCK // _LANES
    nc, blocks = s // q, channels // _SEL_BLOCK

    def spec(block, index, **kw):
        return pl.BlockSpec(block, lambda b_, k_, j_: index(
            b_, nc - 1 - k_ if reverse else k_, j_), **kw)

    return dict(
        grid=(batch, nc, blocks), blocks=blocks, chunks=nc,
        tokens=spec((None, q, rows, _LANES),
                    lambda b_, k_, j_: (b_, k_, j_, 0)),
        scalars=spec((None, None, 1, q * n),
                     lambda b_, k_, j_: (b_, k_, 0, 0),
                     memory_space=pltpu.SMEM),
        a=spec((n, rows, _LANES), lambda b_, k_, j_: (0, j_, 0)),
        d=spec((rows, _LANES), lambda b_, k_, j_: (j_, 0)),
        states=spec((None, None, n, rows, _LANES),
                    lambda b_, k_, j_: (b_, k_, 0, j_, 0)),
        before=spec((None, None, n, rows, _LANES),
                    lambda b_, k_, j_: (b_, jnp.maximum(k_ - 1, 0), 0, j_, 0)),
        per_chunk=spec((None, None, rows, _LANES),
                       lambda b_, k_, j_: (b_, k_, j_, 0)),
        folded=spec((None, q, n, _LANES), lambda b_, k_, j_: (b_, k_, 0, 0)),
        carry=pltpu.VMEM((blocks, n, rows, _LANES), _F32))


def _sel_params(interpret):
    if interpret:
        return None
    # the chunks carry the state; the blocks share a chunk's dB and dC sums
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sel_fwd_call(x4, dt4, a3, b2, c2, d2, *, interpret):
    """``x4``, ``dt4 (b, s, c / 128, 128)`` float32, ``a3 (n, c / 128,
    128)``, ``b2``, ``c2`` ``(b, s / q, 1, q x n)`` float32, ``d2 (c / 128,
    128)``.  Returns ``m`` like ``x4`` and the state LEAVING every chunk
    ``(b, s / q, n, c / 128, 128)``."""
    batch, s, lanes_rows, _ = x4.shape
    n = a3.shape[0]
    sp = _sel_plan(batch, s, lanes_rows * _LANES, n, reverse=False)
    return pl.pallas_call(
        functools.partial(_sel_fwd_kernel, n=n, q=_SEL_CHUNK),
        grid=sp["grid"],
        in_specs=[sp["scalars"], sp["scalars"], sp["tokens"], sp["tokens"],
                  sp["a"], sp["d"]],
        out_specs=[sp["tokens"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct(x4.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (batch, s // _SEL_CHUNK, n, lanes_rows, _LANES),
                       _F32)],
        scratch_shapes=[sp["carry"]],
        compiler_params=_sel_params(interpret), interpret=interpret,
        name="selscan_fwd",
    )(b2, c2, x4, dt4, a3, d2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sel_bwd_call(x4, dt4, a3, b2, c2, d2, states, dm4, *, interpret):
    """Gradients to ``x4`` and ``dt4`` (like them), to ``a3`` and ``d2`` a
    chunk ``(b, s / q, ...)``, and to ``b2``, ``c2`` as partial sums ``(b,
    s, n, 128)`` over the channels that share a lane."""
    batch, s, lanes_rows, _ = x4.shape
    n, q = a3.shape[0], _SEL_CHUNK
    sp = _sel_plan(batch, s, lanes_rows * _LANES, n, reverse=True)
    rows = _SEL_BLOCK // _LANES
    like = jax.ShapeDtypeStruct(x4.shape, _F32)
    folded = jax.ShapeDtypeStruct((batch, s, n, _LANES), _F32)
    return pl.pallas_call(
        functools.partial(_sel_bwd_kernel, n=n, q=q, blocks=sp["blocks"],
                          chunks=sp["chunks"]),
        grid=sp["grid"],
        in_specs=[sp["scalars"], sp["scalars"], sp["tokens"], sp["tokens"],
                  sp["a"], sp["d"], sp["before"], sp["tokens"]],
        out_specs=[sp["tokens"], sp["tokens"], sp["states"],
                   sp["per_chunk"], sp["folded"], sp["folded"]],
        out_shape=[like, like,
                   jax.ShapeDtypeStruct(states.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (batch, s // q, lanes_rows, _LANES), _F32),
                   folded, folded],
        scratch_shapes=[sp["carry"],
                        pltpu.VMEM((q + 1, n, rows, _LANES), _F32),
                        pltpu.VMEM((q, n, rows, _LANES), _F32),
                        pltpu.VMEM((q, n, rows, _LANES), _F32)],
        compiler_params=_sel_params(interpret), interpret=interpret,
        name="selscan_bwd",
    )(b2, c2, x4, dt4, a3, d2, states, dm4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _selscan(x4, dt4, a3, b2, c2, d2, interpret):
    return _sel_fwd_call(x4, dt4, a3, b2, c2, d2, interpret=interpret)


# What a layer checkpoint keeps of the kernels (``models/blocks/mamba1.py``
# hands the name to its policy): the state leaving each chunk, all the
# backward kernel needs beside the inputs.  With it and the scan's output
# held (the block names that), no second ``selscan_fwd`` runs.
SELSCAN_SAVED = ("selscan_states",)


def _selscan_fwd(x4, dt4, a3, b2, c2, d2, interpret):
    m4, states = _sel_fwd_call(x4, dt4, a3, b2, c2, d2, interpret=interpret)
    states = checkpoint_name(states, *SELSCAN_SAVED)
    return (m4, states), (x4, dt4, a3, b2, c2, d2, states)


def _selscan_bwd(interpret, res, cotangents):
    x4, dt4, a3, b2, c2, d2, states = res
    dx4, ddt4, da, dd, db, dc = _sel_bwd_call(
        x4, dt4, a3, b2, c2, d2, states, cotangents[0], interpret=interpret)
    scalars = lambda t: jnp.sum(t, -1).reshape(b2.shape)  # noqa: E731
    return (dx4, ddt4, jnp.sum(da, (0, 1)), scalars(db), scalars(dc),
            jnp.sum(dd, (0, 1)))


_selscan.defvjp(_selscan_fwd, _selscan_bwd)


def selscan_kernels(x, dt, a, b, c, d):
    """``selective_scan`` through the Pallas pair (compiled on the TPU,
    interpreted elsewhere).  XLA pads the sequence to whole chunks (tokens
    whose ``dt`` is 0: no decay, no input), turns x and dt to ``(b, s, c /
    128, 128)`` float32 and B and C to a chunk's scalars, and turns ``m``
    back; the states the forward kernel writes (the one LEAVING each chunk)
    give the statistic."""
    batch, s, channels = x.shape
    n = a.shape[1]
    xs, dts, bs, cs = pad_to_multiple(_SEL_CHUNK, x, dt.astype(_F32), b, c)
    padded = xs.shape[1]
    lanes = lambda t: t.astype(_F32).reshape(  # noqa: E731
        *t.shape[:-1], channels // _LANES, _LANES)
    chunks = lambda t: t.astype(_F32).reshape(  # noqa: E731
        batch, padded // _SEL_CHUNK, 1, _SEL_CHUNK * n)
    m4, states = _selscan(
        lanes(xs), lanes(dts), lanes(a.astype(_F32).T), chunks(bs),
        chunks(cs), lanes(d), attention._interpret_default())
    peak = jnp.max(jnp.abs(jax.lax.stop_gradient(states)))
    return m4.reshape(batch, padded, channels)[:, :s].astype(x.dtype), peak
