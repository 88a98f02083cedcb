"""A Mamba-1 mixer (``mamba1``; arXiv:2312.00752): a SELECTIVE scan, a state
of ``s6_state`` numbers a channel whose decay ``exp(dt_t[c] A[c, n])``
differs by channel, state index and token (``ops/ssm.py::selective_scan``),
as in the first half of a SambaY decoder (arXiv:2507.06607) beside
differential-attention layers.  With ``h`` the block's normed input and
``inner = s6_expand x embed_dim``:

    [xs | z] = h W_in;  xc = silu(conv(xs) + b_conv)
    [dr | B | C] = xc W_x  (dt rank + 2 x state);  dt = softplus(dr W_dt + b_dt)
    m = scan(xc, dt, A = -exp(A_log), B, C) + D xc;  y = (m silu(z)) W_out

Scopes: ``s6_in`` (norm, ``W_in``, the split), ``s6_conv`` (the causal
depthwise convolution with its SiLU — the kernels ``causal_conv_fwd`` /
``_bwd`` where ``ssm.conv_kernels_fit`` —, ``W_x``, ``W_dt``, the softplus),
``s6_scan`` (the recurrence, ``D x`` included: the Pallas pair ``selscan_fwd``
/ ``selscan_bwd`` where the channels fill whole registers, per shard of the
batch under a mesh), ``s6_out`` (the gate, ``W_out``, the add).  dt, A, the
scan and its state are float32.

It PUBLISHES ``m`` — the scan's output with ``D x``, BEFORE the gate — as
``s6_memory``: the gated memory units of later layers (``blocks/gmu.py``)
read it.  The layer checkpoint keeps the input projection's output [xs | z]
(``s6_proj``: bf16, 336 MB a layer at 16384 tokens), the scan's output ``m``
(``s6_scan_out``, 168 MB) and, of the kernels, the state leaving each chunk
(``ssm.SELSCAN_SAVED``, 42 MB): the backward pass then runs no second
``W_in`` product and no second ``selscan_fwd`` (7.7 ms a layer with its
changes of layout on the v5e, PERF.md section 6, PR 74); the convolution,
the two small projections and the softplus ARE run again.  The statistic
``s6_state_absmax`` is the largest ``|H|`` at a chunk's end.
"""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, conv, dt_bias, fold, keyed_ones, residual_out,
    s4d_a_log)
from ray_tpu.models.blocks.residual import add, block_in, norm_shapes
from ray_tpu.ops.ssm import SELSCAN_SAVED, causal_conv1d, selective_scan
from ray_tpu.parallel.sharding import batch_shard_map

MEMORY = "s6_memory"
STATE_ABSMAX = "s6_state_absmax"
STATS = {STATE_ABSMAX: "max"}
SCOPES = ("s6_in", "s6_conv", "s6_scan", "s6_out")
PROJ, SCAN_OUT = "s6_proj", "s6_scan_out"
SAVED = (PROJ, SCAN_OUT, *SELSCAN_SAVED)


def _shapes(cfg):
    """``s6_in`` gives [xs | z] side by side (the published layout of
    ``in_proj``), ``s6_x`` [dt's low rank | B | C] (``x_proj``); ``A_log``
    is a number a channel and state index, ``D`` and dt's bias a channel."""
    d, inner, n, rank = (cfg.embed_dim, cfg.s6_inner, cfg.s6_state,
                         cfg.s6_rank)
    taps = conv(cfg.s6_conv)
    return {
        **norm_shapes(cfg, "s6"),
        "s6_in": Param((d, 2 * inner), ("layer", "kernel_in", "ssm_inner")),
        "s6_conv_w": Param((cfg.s6_conv, inner),
                           ("layer", None, "ssm_inner"), taps),
        "s6_conv_b": Param((inner,), ("layer", "ssm_inner"), taps),
        "s6_x": Param((inner, rank + 2 * n), ("layer", "ssm_inner", None)),
        "s6_dt": Param((rank, inner), ("layer", None, "ssm_inner")),
        "s6_dt_bias": Param((inner,), ("layer", "ssm_inner"), dt_bias),
        "s6_A_log": Param((inner, n), ("layer", "ssm_inner", None),
                          s4d_a_log),
        "s6_D": Param((inner,), ("layer", "ssm_inner"), keyed_ones),
        "s6_out": Param((inner, d), ("layer", "ssm_inner", "kernel_in"),
                        residual_out(cfg)),
    }


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True):
    """-> (the stream, aux, {``s6_memory``: m})."""
    cfg, mesh = ctx.cfg, ctx.mesh
    if ctx.sp_manual:
        raise NotImplementedError(
            "a selective scan inside a region that is manual over 'sp'")
    inner, n, rank = cfg.s6_inner, cfg.s6_state, cfg.s6_rank
    f32 = jnp.float32
    with jax.named_scope("s6_in"):
        h = block_in(x, lp["s6_norm"], cfg, lp.get("s6_norm_bias"))
        xs, z = jnp.split(checkpoint_name(
            h @ lp["s6_in"].astype(cfg.dtype), PROJ), [inner], -1)
    with jax.named_scope("s6_conv"):
        conv1d = causal_conv1d
        if mesh is not None:    # a Pallas kernel has no partitioning rule
            conv1d = batch_shard_map(conv1d, mesh, (3, None, None), 3)
        xc = conv1d(xs, lp["s6_conv_w"], lp["s6_conv_b"])
        dr, bm, cm = jnp.split(xc @ lp["s6_x"].astype(cfg.dtype),
                               [rank, rank + n], -1)
        dt = jax.nn.softplus(
            jnp.dot(dr, lp["s6_dt"].astype(cfg.dtype),
                    preferred_element_type=f32)
            + lp["s6_dt_bias"].astype(f32))
    with jax.named_scope("s6_scan"):
        scan = selective_scan
        if mesh is not None:
            scan = batch_shard_map(
                scan, mesh, (3, 3, None, 3, 3, None), (3, None),
                reduce=jax.lax.pmax)
        m, peak = scan(xc, dt, -jnp.exp(lp["s6_A_log"].astype(f32)), bm, cm,
                       lp["s6_D"])
        m = checkpoint_name(m, SCAN_OUT)
        aux = fold(aux, {STATE_ABSMAX: peak}, STATS)
    with jax.named_scope("s6_out"):
        y = (m.astype(f32) * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        return add(ctx, x, y @ lp["s6_out"].astype(cfg.dtype),
                   residual), aux, {MEMORY: m}


BLOCK = Block(_shapes, _apply, saved=SAVED, scopes=SCOPES,
              stats=lambda cfg: STATS, publishes=(MEMORY,))
