"""A Mamba-2 state-space mixer (``layer_types``: ``mamba``;
``ops/ssm.py``), as in granite-4.0-h's layers beside an attention layer
every tenth.  Scopes: ``ssm_in`` (norm, the one input projection, its
split), ``ssm_conv`` (the convolution over x, B, C with its SiLU — the
kernels ``causal_conv_fwd`` / ``causal_conv_bwd`` where
``ssm.conv_kernels_fit``, per shard of the batch under a mesh —; dt's
softplus), ``ssm_scan`` (the chunked scan, ``D x`` included: Pallas kernels
where ``ssm.kernels_fit`` — heads that fill whole lane blocks inside each
of the ``ssm_groups`` groups, whose B and C go in side by side as the
convolution's split leaves them —, per shard of the batch under a mesh),
``ssm_out`` (the norm of the GATED output — gate first, then a norm over
each group's own channels, ``ssm_groups`` equal parts of the inner width:
the whole of it for one group; several groups are one rule that never
leaves ``(b, s, inner)``, Pallas kernels where ``ssm.norm_kernels_fit``,
per shard of the batch under a mesh as the scan —, the output projection,
the residual add).

The layer checkpoint keeps the input projection's output [z | xBC | dt]
(``ssm_proj``: bf16, 139 MB a layer at 8192 tokens).  With it the backward
pass runs no second ``ssm_in`` matmul; the convolution, the scan and the
gated norm ARE run again: the convolution's and the scan's intermediates
are several times that size, and the norm's rule keeps nothing but its
arguments — the rerun scan's output and ``z``, a slice of ``ssm_proj``.
On the v5e: 8.8 ms of a 507 ms step for 1.25 GB held, 2.4 GB of program
(PERF.md §6, PR 30).
"""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, a_log, conv, dt_bias, keyed_ones, ones, residual_out)
from ray_tpu.models.blocks.residual import add
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssm import causal_conv1d, gated_rms_norm, ssd_chunked
from ray_tpu.parallel.sharding import batch_shard_map

SAVED = ("ssm_proj",)


def _shapes(cfg):
    """``ssm_in`` gives [z | x B C | dt] side by side (the published layout
    of ``in_proj``); the convolution runs over x, B and C; ``dt_bias``,
    ``A_log`` and ``D`` are a number a head, initialised as the Mamba-2
    reference code does.  ``ssm_inner`` maps to no mesh axis: a tp split
    has to cut each part of the one projection (later)."""
    d, inner, width = cfg.embed_dim, cfg.ssm_inner, cfg.ssm_conv_dim
    taps = conv(cfg.ssm_conv)
    return {
        "ssm_norm": Param((d,), ("layer", "embed"), ones),
        "ssm_in": Param((d, inner + width + cfg.ssm_heads),
                        ("layer", "kernel_in", "ssm_inner")),
        "conv_w": Param((cfg.ssm_conv, width), ("layer", None, "ssm_inner"),
                        taps),
        "conv_b": Param((width,), ("layer", "ssm_inner"), taps),
        "dt_bias": Param((cfg.ssm_heads,), ("layer", None), dt_bias),
        "A_log": Param((cfg.ssm_heads,), ("layer", None), a_log),
        "D": Param((cfg.ssm_heads,), ("layer", None), keyed_ones),
        "gate_norm": Param((inner,), ("layer", "ssm_inner"), ones),
        "ssm_out": Param((inner, d), ("layer", "ssm_inner", "kernel_in"),
                         residual_out(cfg)),
    }


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True):
    cfg, mesh = ctx.cfg, ctx.mesh
    b, s = x.shape[0], x.shape[1]
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    f32 = jnp.float32
    # a Pallas kernel has no partitioning rule: per shard of the batch
    per_shard = mesh is not None and not ctx.sp_manual
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["ssm_norm"], cfg.norm_eps)
        zxbcdt = checkpoint_name(h @ lp["ssm_in"].astype(cfg.dtype), *SAVED)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + cfg.ssm_conv_dim], -1)
    with jax.named_scope("ssm_conv"):
        conv = causal_conv1d
        if per_shard:
            conv = batch_shard_map(conv, mesh, (3, None, None), 3)
        xbc = conv(xbc, lp["conv_w"], lp["conv_b"])
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], -1)
    with jax.named_scope("ssm_scan"):
        scan = lambda *t: ssd_chunked(*t, chunk=cfg.ssm_chunk)  # noqa: E731
        if per_shard:
            scan = batch_shard_map(scan, mesh, (4, 3, None, 4, 4, None), 4)
        y = scan(
            xs.reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim), dt,
            -jnp.exp(lp["A_log"].astype(f32)),
            bm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            cm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            lp["D"])
    with jax.named_scope("ssm_out"):
        norm = lambda *t: gated_rms_norm(  # noqa: E731
            *t, cfg.norm_eps, cfg.ssm_groups)
        if per_shard:
            norm = batch_shard_map(norm, mesh, (3, 3, None), 3)
        y = norm(y.reshape(b, s, inner), z, lp["gate_norm"])
        return add(ctx, x, y @ lp["ssm_out"].astype(cfg.dtype),
                   residual), aux


BLOCK = Block(_shapes, _apply, saved=SAVED,
              scopes=("ssm_in", "ssm_conv", "ssm_scan", "ssm_out"))
