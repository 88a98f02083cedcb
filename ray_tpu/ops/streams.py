"""The n-stream residual's two halves (manifold-constrained
hyper-connections, arXiv:2512.24880 §4) as operations with a written-out
backward pass.

A token's residual is ``n`` streams of ``d`` numbers lying side by side,
``X (..., n d)``.  A block reads ``x = sum_j pre_j X_j`` and what it makes,
``y``, is written back as ``X'_i = post_i y + sum_j res_ij X_j``; the maps
``pre (n)``, ``post (n)``, ``res (n, n)`` are made per token from the streams
themselves:

    r     = rsqrt(mean(X^2) + norm_eps)            X as ONE vector of n d
    a     = r (X proj) * scale + bias              proj (n d, 2 n + n^2)
    pre   = sigmoid(a[:n])        post = 2 sigmoid(a[n:2n])
    res   = sinkhorn(exp(clip(a[2n:])))            ``iters`` rounds, rows then
                                                   columns over their sum + eps

``streams_read`` is the half before the block, ``(X, proj, scale, bias) ->
(x, maps, X)``, ``streams_write`` the half after it, ``(X, y, maps) -> X'``.
Each is ONE ``jax.custom_vjp``: it reads the streams once a pass in their own
dtype and holds no float32 or normed copy of them in memory (autodiff of the
plain sums moves float32 copies of the streams through the norm's gradient
four times and reads them once a slice in the mix's: PERF.md §6, PR 37).
``streams_read`` hands ``X`` on unchanged so that what ``streams_write``'s
backward sends round the block (``sum_i res_ij dX'_i``) arrives in
``streams_read``'s as a cotangent and is added where ``dX`` is written, not
in a pass of its own.  Residuals: the arguments, and per token ``r (X proj)``
and ``r`` — the rounds are run again in the backward pass and differentiated
exactly, round by round.

``maps (..., W)`` float32, ``W`` = ``maps_width(n)``: a token's numbers in
groups of 8 columns — pre, post, then each row of res — the rest zeros, so
that a group is a sublane tile once the tokens are minor.

Two forms of the same sums, chosen by what a call's shapes show
(``kernels_fit``): four Pallas kernels (``hc_read_fwd``, ``hc_read_bwd``,
``hc_write_fwd``, ``hc_write_bwd``; interpreted off the chip) where ``d``
fills whole lane blocks, ``n`` is at most 8 and the tokens divide into tiles
of 128; elsewhere, and under a mesh, ``form="xla"``: plain XLA under the same
``custom_vjp``.

The kernels: grid ``(tokens / tile,)``, a step holding a tile's ``(tile,
n d)`` streams in VMEM and walking them in ``fori_loop``s over strips of
lanes (every stream's strip at one offset); the maps are made with the
tokens minor — the small ``(tile, 128)`` array of a token's ``a`` is
transposed in VMEM, each group ``(8, tile)`` — and the rounds are a
``fori_loop`` whose states the backward kernel keeps in a VMEM scratch.
``hc_read_bwd`` also accumulates the projection's gradient ``(128, n d)``
float32 over the grid in VMEM, so the grid runs in order.

Precision: the statistics, the maps, the rounds and every sum are float32;
``X``, ``x``, ``y``, ``X'`` and their gradients keep the streams' dtype; the
products with ``proj`` take operands in the streams' dtype and accumulate in
float32 (``X`` itself, not a rounded normed copy, meets ``proj``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention
from ray_tpu.ops.layers import sinkhorn

_F32 = jnp.float32
_LANES = 128
_GROUP = 8             # columns a group of a token's maps takes
_TILES = (256, 128)    # tokens a grid step, the first that divides them
_STRIPS = (512, 256, 128)   # lanes a strip of a stream, likewise


class Plan(NamedTuple):
    """What is static in a call: the structure's numbers and the form."""
    n: int
    norm_eps: float
    clamp_min: float
    clamp_max: float
    iters: int
    eps: float
    tile: int          # 0: the XLA form
    interpret: bool


def maps_width(n: int) -> int:
    """Columns of ``maps``: ``2 + n`` groups of 8 and one spare column (the
    kernels keep ``r`` beside ``r (X proj)``), in whole lane blocks."""
    return -(-(_GROUP * (2 + n) + 1) // _LANES) * _LANES


def kernels_fit(n: int, d: int, tokens: int) -> bool:
    """Whether the Pallas kernels take a call: every stream whole lane
    blocks, a row of res inside one group, the tokens in whole tiles."""
    return (1 <= n <= _GROUP and d > 0 and d % _LANES == 0
            and tokens % _TILES[-1] == 0)


def _first_dividing(sizes, value):
    return next(s for s in sizes if value % s == 0)


# ------------------------------------------------ the parameters, laid out

def _columns(proj, scale, bias, n):
    """``proj (n d, n (2 + n))``, ``scale (3,)``, ``bias (n (2 + n),)`` laid
    over the maps' columns: ``(n d, W)``, ``(W,)``, ``(W,)``, zeros where no
    number of a map lies."""
    groups, width = 2 + n, maps_width(n)

    def spread(v):  # (..., groups * n) -> (..., W)
        v = v.reshape(*v.shape[:-1], groups, n)
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, _GROUP - n)])
        v = v.reshape(*v.shape[:-2], groups * _GROUP)
        return jnp.pad(v, [(0, 0)] * (v.ndim - 1)
                       + [(0, width - groups * _GROUP)])

    each = jnp.concatenate([scale[:2], jnp.broadcast_to(scale[2], (n,))])
    return (spread(proj), spread(jnp.repeat(each.astype(_F32), n)),
            spread(bias.astype(_F32)))


# ------------------------------------------------------------ the XLA form

def _groups(maps, n):
    """``maps (..., W)`` with the tokens minor: ``(2 + n, n, ...)``."""
    t = jnp.moveaxis(maps, -1, 0)[:_GROUP * (2 + n)]
    return t.reshape(2 + n, _GROUP, *t.shape[1:])[:, :n]


def _ungroup(g, n):
    """``_groups``' inverse (zeros in the other columns)."""
    g = jnp.pad(g, [(0, 0), (0, _GROUP - n)] + [(0, 0)] * (g.ndim - 2))
    g = g.reshape((2 + n) * _GROUP, *g.shape[2:])
    g = jnp.pad(g, [(0, maps_width(n) - g.shape[0])]
                + [(0, 0)] * (g.ndim - 1))
    return jnp.moveaxis(g, 0, -1)


def _maps_of(a, plan: Plan):
    """The maps ``(2 + n, n, ...)`` (tokens minor) of ``a (..., W)``."""
    g = _groups(a, plan.n)
    res = sinkhorn(jnp.clip(g[2:], plan.clamp_min, plan.clamp_max),
                   plan.iters, plan.eps)
    return jnp.concatenate([jax.nn.sigmoid(g[:1]),
                            2.0 * jax.nn.sigmoid(g[1:2]), res])


def _slices(xs, n):
    d = xs.shape[-1] // n
    return [xs[..., j * d:(j + 1) * d].astype(_F32) for j in range(n)]


def _read_fwd_xla(plan, xs, pw, sw, bw):
    n, width = plan.n, xs.shape[-1]
    ss = jnp.sum(jnp.square(xs.astype(_F32)), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ss / width + plan.norm_eps)
    v = jnp.dot(xs, pw.astype(xs.dtype), preferred_element_type=_F32) * r
    maps = _maps_of(v * sw + bw, plan)
    x = sum(maps[0, j][..., None] * xj
            for j, xj in enumerate(_slices(xs, n))).astype(xs.dtype)
    return x, _ungroup(maps, n), v, r


def _read_bwd_xla(plan, xs, pw, sw, bw, v, r, dx, dmaps, gx):
    n, width = plan.n, xs.shape[-1]
    a = v * sw + bw
    maps, back = jax.vjp(lambda a_: _maps_of(a_, plan), a)
    dx32, xjs = dx.astype(_F32), _slices(xs, n)
    dg = _groups(dmaps, n)
    dg = dg.at[0].add(jnp.stack([jnp.sum(dx32 * xj, axis=-1) for xj in xjs]))
    da, = back(dg)
    dv = da * sw
    lead = tuple(range(da.ndim - 1))
    # through r: d r / d X = -r^3 X / (n d)
    through_r = jnp.sum(dv * v, axis=-1, keepdims=True) * jnp.square(r) / width
    du = (dv * r).astype(xs.dtype)
    p = pw.astype(xs.dtype)
    projected = jnp.dot(du, p.T, preferred_element_type=xs.dtype)
    d = width // n
    dxs = jnp.concatenate([
        (gx[..., j * d:(j + 1) * d].astype(_F32) + maps[0, j][..., None] * dx32
         + projected[..., j * d:(j + 1) * d].astype(_F32)
         - through_r * xj).astype(xs.dtype)
        for j, xj in enumerate(xjs)], axis=-1)
    dpw = jnp.tensordot(xs, du, (lead, lead), preferred_element_type=_F32)
    return (dxs, dpw.astype(pw.dtype), jnp.sum(da * v, axis=lead),
            jnp.sum(da, axis=lead))


def _write_fwd_xla(plan, xs, y, maps):
    n = plan.n
    g, y32, xjs = _groups(maps, n), y.astype(_F32), _slices(xs, n)
    return jnp.concatenate([
        (g[1, i][..., None] * y32
         + sum(g[2 + i, j][..., None] * xj for j, xj in enumerate(xjs))
         ).astype(xs.dtype) for i in range(n)], axis=-1)


def _write_bwd_xla(plan, xs, y, maps, dout):
    n = plan.n
    g, y32, xjs = _groups(maps, n), y.astype(_F32), _slices(xs, n)
    dis = _slices(dout, n)
    dy = sum(g[1, i][..., None] * di for i, di in enumerate(dis))
    gx = jnp.concatenate([
        sum(g[2 + i, j][..., None] * di for i, di in enumerate(dis)
            ).astype(xs.dtype) for j in range(n)], axis=-1)
    dpost = jnp.stack([jnp.sum(di * y32, axis=-1) for di in dis])
    dres = jnp.stack([jnp.stack([jnp.sum(di * xj, axis=-1) for xj in xjs])
                      for di in dis])
    dg = jnp.concatenate([jnp.zeros_like(dpost)[None], dpost[None], dres])
    return gx, dy.astype(y.dtype), _ungroup(dg, n)


# --------------------------------------------------------- the Pallas form
#
# A token's small numbers live in two layouts: as COLUMNS ``(tile, 1)`` of a
# ``(tile, 128)`` array where they scale a token's row of the streams, and,
# transposed, as groups ``(8, tile)`` with the tokens along the lanes where
# the maps are made (a row of res is one group: its sum runs down the
# sublanes, a column's sum across the groups).

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _col(v, c):
    """Column ``c`` of ``v (tile, 128)`` as ``(tile, 1)``."""
    return jnp.sum(jnp.where(_iota(v.shape, 1) == c, v, 0.0), axis=1,
                   keepdims=True)


def _from_cols(cols, tile):
    """``(tile, 128)`` holding ``cols[c] (tile, 1)`` in column ``c``."""
    lane = _iota((tile, _LANES), 1)
    out = jnp.zeros((tile, _LANES), _F32)
    for c, v in cols.items():
        out = jnp.where(lane == c, v, out)
    return out


def _lane_blocks(v):
    """``v (tile, w)`` summed over its blocks of 128 lanes: ``(tile, 128)``,
    adds of whole registers."""
    out = v[:, :_LANES]
    for k in range(1, v.shape[1] // _LANES):
        out = out + v[:, k * _LANES:(k + 1) * _LANES]
    return out


def _dot(a, b, widen=False):
    """``a b`` accumulated in float32; ``widen``: the operands as float32
    first (the same products: the interpreter's CPU refuses some bfloat16
    products inside a loop that the chip takes)."""
    if widen:
        a, b = a.astype(_F32), b.astype(_F32)
    return jnp.dot(a, b, preferred_element_type=_F32)


def _strip(j, c, d, w):
    """Lanes ``c w .. (c + 1) w`` of stream ``j``."""
    return pl.ds(pl.multiple_of(j * d + c * w, _LANES), w)


def _group(t, g):
    return t[g * _GROUP:(g + 1) * _GROUP]


def _start(a_t, plan: Plan):
    """What the rounds start from: ``exp(clip(a))`` of each row of res
    ``(8, tile)``, zeros below a row's ``n`` numbers."""
    live = _iota((_GROUP, a_t.shape[1]), 0) < plan.n
    return tuple(
        jnp.where(live, jnp.exp(jnp.clip(_group(a_t, 2 + i), plan.clamp_min,
                                         plan.clamp_max)), 0.0)
        for i in range(plan.n))


def _rows_normed(ms, eps):
    return tuple(m / (jnp.sum(m, axis=0, keepdims=True) + eps) for m in ms)


def _columns_normed(ms, eps):
    total = functools.reduce(jnp.add, ms) + eps
    return tuple(m / total for m in ms)


def _maps_t(a_t, plan: Plan, keep=None):
    """``(128, tile)``: the maps of ``a_t (128, tile)``, both with the
    tokens along the lanes.  ``keep (2 iters, n, 8, tile)``: a scratch that
    takes what enters every half round."""
    n, tile = plan.n, a_t.shape[1]
    live = _iota((_GROUP, tile), 0) < n

    def one_round(k, ms):
        if keep is not None:
            for i in range(n):
                keep[2 * k, i] = ms[i]
        ms = _rows_normed(ms, plan.eps)
        if keep is not None:
            for i in range(n):
                keep[2 * k + 1, i] = ms[i]
        return _columns_normed(ms, plan.eps)

    res = jax.lax.fori_loop(0, plan.iters, one_round, _start(a_t, plan))
    return jnp.concatenate([
        jnp.where(live, jax.nn.sigmoid(_group(a_t, 0)), 0.0),
        jnp.where(live, 2.0 * jax.nn.sigmoid(_group(a_t, 1)), 0.0), *res,
        jnp.zeros((_LANES - _GROUP * (2 + n), tile), _F32)], axis=0)


def _maps_t_backward(a_t, dmaps_t, plan: Plan, keep):
    """The gradient to ``a_t`` of ``_maps_t`` (which has filled ``keep``),
    round by round from the last."""
    n, tile = plan.n, a_t.shape[1]
    eps = plan.eps
    live = _iota((_GROUP, tile), 0) < n

    def one_round(k, dms):
        k = plan.iters - 1 - k
        ms = tuple(keep[2 * k + 1, i] for i in range(n))
        inv = 1.0 / (functools.reduce(jnp.add, ms) + eps)
        fed = functools.reduce(jnp.add, [dm * m * inv
                                         for dm, m in zip(dms, ms)])
        dms = tuple((dm - fed) * inv for dm in dms)
        out = []
        for i in range(n):
            m = keep[2 * k, i]
            inv_i = 1.0 / (jnp.sum(m, axis=0, keepdims=True) + eps)
            fed_i = jnp.sum(dms[i] * m * inv_i, axis=0, keepdims=True)
            # below a row's n numbers nothing is fed and nothing may grow
            out.append(jnp.where(live, (dms[i] - fed_i) * inv_i, 0.0))
        return tuple(out)

    dms = jax.lax.fori_loop(
        0, plan.iters, one_round,
        tuple(_group(dmaps_t, 2 + i) for i in range(n)))
    das = []
    for i in range(n):
        a_i = _group(a_t, 2 + i)
        inside = live & (a_i > plan.clamp_min) & (a_i < plan.clamp_max)
        das.append(jnp.where(inside, dms[i] * keep[0, i], 0.0))
    pre, post = jax.nn.sigmoid(_group(a_t, 0)), jax.nn.sigmoid(_group(a_t, 1))
    return jnp.concatenate([
        jnp.where(live, _group(dmaps_t, 0) * pre * (1.0 - pre), 0.0),
        jnp.where(live, _group(dmaps_t, 1) * 2.0 * post * (1.0 - post), 0.0),
        *das, jnp.zeros((_LANES - _GROUP * (2 + n), tile), _F32)], axis=0)


def _read_fwd_kernel(x_ref, p_ref, s_ref, b_ref, o_ref, maps_ref, stat_ref,
                     *, plan: Plan, d, w):
    n, tile = plan.n, x_ref.shape[0]

    def stats(c, carry):
        squares, u = carry
        for j in range(n):
            at = _strip(j, c, d, w)
            xj = x_ref[:, at]
            u = u + _dot(xj, p_ref[at, :])
            squares = squares + _lane_blocks(jnp.square(xj.astype(_F32)))
        return squares, u

    zero = jnp.zeros((tile, _LANES), _F32)
    squares, u = jax.lax.fori_loop(0, d // w, stats, (zero, zero))
    r = jax.lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True) / (n * d)
                      + plan.norm_eps)
    v = u * r
    maps = _maps_t((v * s_ref[...] + b_ref[...]).T, plan).T
    maps_ref[...] = maps
    stat_ref[...] = jnp.where(_iota(v.shape, 1) == _GROUP * (2 + n), r, v)
    pre = [_col(maps, j) for j in range(n)]

    def mix(c, carry):
        o_ref[:, pl.ds(pl.multiple_of(c * w, _LANES), w)] = sum(
            pre[j] * x_ref[:, _strip(j, c, d, w)].astype(_F32)
            for j in range(n)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, d // w, mix, 0)


def _read_bwd_kernel(x_ref, gx_ref, dx_ref, dmaps_ref, stat_ref, pt_ref,
                     s_ref, b_ref, dxs_ref, dpt_ref, dsb_ref, keep,
                     *, plan: Plan, d, w):
    n, tile = plan.n, x_ref.shape[0]
    spare = _GROUP * (2 + n)

    @pl.when(pl.program_id(0) == 0)
    def _first_step():
        dpt_ref[...] = jnp.zeros_like(dpt_ref)
        dsb_ref[...] = jnp.zeros_like(dsb_ref)

    def read(c, sums):
        dx = dx_ref[:, pl.ds(pl.multiple_of(c * w, _LANES), w)].astype(_F32)
        return tuple(
            s + jnp.sum(dx * x_ref[:, _strip(j, c, d, w)].astype(_F32),
                        axis=1, keepdims=True) for j, s in enumerate(sums))

    dpre = jax.lax.fori_loop(0, d // w, read,
                             (jnp.zeros((tile, 1), _F32),) * n)
    stat = stat_ref[...]
    r = _col(stat, spare)
    v = jnp.where(_iota(stat.shape, 1) == spare, 0.0, stat)
    scale = s_ref[...]
    a_t = (v * scale + b_ref[...]).T
    maps = _maps_t(a_t, plan, keep).T  # fills ``keep``
    dmaps = dmaps_ref[...] + _from_cols(dict(enumerate(dpre)), tile)
    da = _maps_t_backward(a_t, dmaps.T, plan, keep).T
    dsb_ref[0:1, :] += jnp.sum(da, axis=0, keepdims=True)
    dsb_ref[1:2, :] += jnp.sum(da * v, axis=0, keepdims=True)
    dv = da * scale
    through_r = (jnp.sum(dv * v, axis=1, keepdims=True) * jnp.square(r)
                 / (n * d))
    du = dv * r
    du_t = du.T.astype(x_ref.dtype)
    du = du.astype(x_ref.dtype)
    pre = [_col(maps, j) for j in range(n)]

    def write(c, carry):
        dx = dx_ref[:, pl.ds(pl.multiple_of(c * w, _LANES), w)].astype(_F32)
        for j in range(n):
            at = _strip(j, c, d, w)
            xj = x_ref[:, at]
            dxs_ref[:, at] = (
                gx_ref[:, at].astype(_F32) + pre[j] * dx
                + _dot(du, pt_ref[:, at], plan.interpret)
                - through_r * xj.astype(_F32)
            ).astype(dxs_ref.dtype)
            dpt_ref[:, at] += _dot(du_t, xj, plan.interpret)
        return carry

    jax.lax.fori_loop(0, d // w, write, 0)


def _post_and_res(maps, n):
    """``post[i]``, ``res[i][j]`` as columns of ``maps (tile, 128)``."""
    return ([_col(maps, _GROUP + i) for i in range(n)],
            [[_col(maps, _GROUP * (2 + i) + j) for j in range(n)]
             for i in range(n)])


def _write_fwd_kernel(x_ref, y_ref, maps_ref, o_ref, *, plan: Plan, d, w):
    n = plan.n
    post, res = _post_and_res(maps_ref[...], n)

    def mix(c, carry):
        y = y_ref[:, pl.ds(pl.multiple_of(c * w, _LANES), w)].astype(_F32)
        xjs = [x_ref[:, _strip(j, c, d, w)].astype(_F32) for j in range(n)]
        for i in range(n):
            o_ref[:, _strip(i, c, d, w)] = (
                post[i] * y + sum(res[i][j] * xjs[j] for j in range(n))
            ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, d // w, mix, 0)


def _write_bwd_kernel(g_ref, x_ref, y_ref, maps_ref, gx_ref, dy_ref,
                      dmaps_ref, *, plan: Plan, d, w):
    n, tile = plan.n, x_ref.shape[0]
    post, res = _post_and_res(maps_ref[...], n)

    def mix(c, sums):
        at = pl.ds(pl.multiple_of(c * w, _LANES), w)
        y = y_ref[:, at].astype(_F32)
        xjs = [x_ref[:, _strip(j, c, d, w)].astype(_F32) for j in range(n)]
        dis = [g_ref[:, _strip(i, c, d, w)].astype(_F32) for i in range(n)]
        dy_ref[:, at] = sum(post[i] * dis[i] for i in range(n)
                            ).astype(dy_ref.dtype)
        for j in range(n):
            gx_ref[:, _strip(j, c, d, w)] = sum(
                res[i][j] * dis[i] for i in range(n)).astype(gx_ref.dtype)
        inner = [dis[i] * y for i in range(n)] + [
            dis[i] * xjs[j] for i in range(n) for j in range(n)]
        return tuple(s + jnp.sum(p, axis=1, keepdims=True)
                     for s, p in zip(sums, inner))

    sums = jax.lax.fori_loop(0, d // w, mix,
                             (jnp.zeros((tile, 1), _F32),) * (n + n * n))
    cols = {_GROUP + i: sums[i] for i in range(n)}
    cols.update({_GROUP * (2 + i) + j: sums[n + i * n + j]
                 for i in range(n) for j in range(n)})
    dmaps_ref[...] = _from_cols(cols, tile)


def _launch(body, name, plan: Plan, xs, args, ins, outs, *, order="parallel",
            scratch=()):
    """``pallas_call`` of ``body`` over the tiles of ``xs (T, n d)``.
    ``ins`` / ``outs`` say what each argument / result is: ``"streams"``
    ``(T, n d)``, ``"stream"`` ``(T, d)`` (both in ``xs.dtype``),
    ``"small"`` ``(T, 128)`` float32, each cut into the grid step's tile —
    or a float32 result's whole shape, an argument seen whole (None)."""
    tokens, width = xs.shape
    d = width // plan.n
    cols = {"streams": width, "stream": d, "small": _LANES}

    def spec(kind, shape):
        if kind in cols:
            return pl.BlockSpec((plan.tile, cols[kind]), lambda t: (t, 0))
        return pl.BlockSpec(shape, lambda t: (0, 0))

    def result(kind):
        if kind in cols:
            return jax.ShapeDtypeStruct(
                (tokens, cols[kind]), _F32 if kind == "small" else xs.dtype)
        return jax.ShapeDtypeStruct(kind, _F32)

    results = [result(kind) for kind in outs]
    return pl.pallas_call(
        functools.partial(body, plan=plan, d=d,
                          w=_first_dividing(_STRIPS, d)),
        grid=(tokens // plan.tile,),
        in_specs=[spec(kind, a.shape) for kind, a in zip(ins, args)],
        out_specs=[spec(kind, r.shape) for kind, r in zip(outs, results)],
        out_shape=results,
        scratch_shapes=list(scratch),
        compiler_params=None if plan.interpret else pltpu.CompilerParams(
            dimension_semantics=(order,),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=plan.interpret,
        name=name,
    )(*args)


@functools.partial(jax.jit, static_argnames=("plan",))
def _read_fwd_call(xs, pw, sw, bw, *, plan: Plan):
    """``xs (T, n d)``; ``pw (n d, 128)`` in its dtype, ``sw``, ``bw`` ``(1,
    128)`` float32.  -> ``x (T, d)``, ``maps``, ``stat`` ``(T, 128)``."""
    return _launch(_read_fwd_kernel, "hc_read_fwd", plan, xs,
                   (xs, pw, sw, bw), ("streams", None, None, None),
                   ("stream", "small", "small"))


@functools.partial(jax.jit, static_argnames=("plan",))
def _read_bwd_call(xs, gx, dx, dmaps, stat, pt, sw, bw, *, plan: Plan):
    """-> ``dxs`` like ``xs``, the gradient to ``pt (128, n d)`` float32,
    and ``(8, 128)`` float32: row 0 the gradient to ``bw``, row 1 to ``sw``.
    Both are summed over the grid's steps, which therefore run in order."""
    return _launch(
        _read_bwd_kernel, "hc_read_bwd", plan, xs,
        (xs, gx, dx, dmaps, stat, pt, sw, bw),
        ("streams", "streams", "stream", "small", "small", None, None, None),
        ("streams", pt.shape, (_GROUP, _LANES)), order="arbitrary",
        scratch=[pltpu.VMEM((2 * plan.iters, plan.n, _GROUP, plan.tile),
                            _F32)])


@functools.partial(jax.jit, static_argnames=("plan",))
def _write_fwd_call(xs, y, maps, *, plan: Plan):
    return _launch(_write_fwd_kernel, "hc_write_fwd", plan, xs,
                   (xs, y, maps), ("streams", "stream", "small"),
                   ("streams",))[0]


@functools.partial(jax.jit, static_argnames=("plan",))
def _write_bwd_call(dout, xs, y, maps, *, plan: Plan):
    """-> what goes round the block (like ``xs``), ``dy``, ``dmaps``."""
    return _launch(_write_bwd_kernel, "hc_write_bwd", plan, xs,
                   (dout, xs, y, maps),
                   ("streams", "streams", "stream", "small"),
                   ("streams", "stream", "small"))


def _flat(t):
    return t.reshape(-1, t.shape[-1])


def _read_fwd_kernels(plan, xs, pw, sw, bw):
    x, maps, stat = _read_fwd_call(
        _flat(xs), pw.astype(xs.dtype), sw[None], bw[None], plan=plan)
    lead = xs.shape[:-1]
    return (x.reshape(*lead, -1), maps.reshape(*lead, -1),
            stat.reshape(*lead, -1))


def _read_bwd_kernels(plan, xs, pw, sw, bw, stat, dx, dmaps, gx):
    dxs, dpt, dsb = _read_bwd_call(
        _flat(xs), _flat(gx), _flat(dx), _flat(dmaps), _flat(stat),
        pw.astype(xs.dtype).T, sw[None], bw[None], plan=plan)
    return dxs.reshape(xs.shape), dpt.T.astype(pw.dtype), dsb[1], dsb[0]


# ------------------------------------------------- the two operations

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _read(plan: Plan, xs, pw, sw, bw):
    return _read_fwd(plan, xs, pw, sw, bw)[0]


def _read_fwd(plan: Plan, xs, pw, sw, bw):
    if plan.tile:
        x, maps, stat = _read_fwd_kernels(plan, xs, pw, sw, bw)
        saved = (stat,)
    else:
        x, maps, *saved = _read_fwd_xla(plan, xs, pw, sw, bw)
    return (x, maps, xs), (xs, pw, sw, bw, *saved)


def _read_bwd(plan: Plan, saved, cotangents):
    bwd = _read_bwd_kernels if plan.tile else _read_bwd_xla
    return bwd(plan, *saved, *cotangents)


_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _write(plan: Plan, xs, y, maps):
    if plan.tile:
        return _write_fwd_call(_flat(xs), _flat(y), _flat(maps),
                               plan=plan).reshape(xs.shape)
    return _write_fwd_xla(plan, xs, y, maps)


def _write_fwd(plan: Plan, xs, y, maps):
    return _write(plan, xs, y, maps), (xs, y, maps)


def _write_bwd(plan: Plan, saved, dout):
    xs, y, maps = saved
    if not plan.tile:
        return _write_bwd_xla(plan, xs, y, maps, dout)
    gx, dy, dmaps = _write_bwd_call(_flat(dout), _flat(xs), _flat(y),
                                    _flat(maps), plan=plan)
    return gx.reshape(xs.shape), dy.reshape(y.shape), dmaps.reshape(maps.shape)


_write.defvjp(_write_fwd, _write_bwd)


def plan_for(xs, n: int, *, norm_eps: float, clamp, iters: int, eps: float,
             form: Optional[str] = None, tile: Optional[int] = None) -> Plan:
    """The plan of a call on ``xs (..., n d)``: ``form`` ``"kernels"`` |
    ``"xla"`` | None (the kernels where ``kernels_fit``), ``tile`` the
    kernels' tokens a grid step (None: the largest of 256, 128 that divides
    the tokens)."""
    d, tokens = xs.shape[-1] // n, xs.size // xs.shape[-1]
    if form is None:
        form = "kernels" if kernels_fit(n, d, tokens) else "xla"
    if form == "kernels":
        if not kernels_fit(n, d, tokens):
            raise ValueError(
                f"the stream kernels take n <= {_GROUP}, d in whole blocks "
                f"of {_LANES} lanes and tokens in tiles of {_TILES[-1]}; got "
                f"n={n}, d={d}, {tokens} tokens")
        tile = tile or _first_dividing(_TILES, tokens)
    return Plan(n, float(norm_eps), float(clamp[0]), float(clamp[1]),
                int(iters), float(eps), tile if form == "kernels" else 0,
                attention._interpret_default())


def streams_read(plan: Plan, xs, proj, scale, bias):
    """The half before a block: ``(x (..., d), maps (..., W), xs)`` — the
    block's input read off the streams, the token's maps, and the streams
    handed on for ``streams_write`` (take THEM, not the argument: the
    gradient that goes round the block then arrives here)."""
    return _read(plan, xs, *_columns(proj, scale, bias, plan.n))


def streams_write(plan: Plan, xs, y, maps):
    """The half after a block: ``X'_i = post_i y + sum_j res_ij X_j``."""
    return _write(plan, xs, y, maps)


def maps_of(maps, n: int):
    """``maps (..., W)`` as ``(pre (..., n), post (..., n), res (..., n,
    n))``."""
    g = jnp.moveaxis(_groups(maps, n), (0, 1), (-2, -1))
    return g[..., 0, :], g[..., 1, :], g[..., 2:, :]
