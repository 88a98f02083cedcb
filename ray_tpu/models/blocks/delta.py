"""A gated delta-rule linear-attention mixer (``layer_types``:
``linear_attention``; ``ops/delta.py``, arXiv:2412.06464), as in
Olmo-Hybrid's layers beside ``full_attention`` ones.  Scopes: ``gdn_in``
(the block's norm where it norms its input, the one projection, its
split), ``gdn_conv`` (the convolution over q, k, v with its SiLU — the
kernels ``causal_conv_fwd`` / ``causal_conv_bwd`` where
``ssm.conv_kernels_fit``, per shard of the batch under a mesh —, the L2
norm of each head's q and k — q then times ``key_dim ** -0.5`` —, ``beta =
sigmoid(b)``, twice that where the rule may have negative eigenvalues, and
the log-decay ``g = -exp(A_log) softplus(a + dt_bias)``), ``gdn_scan`` (the
chunked rule: Pallas kernels where ``delta.kernels_fit``, per shard of the
batch under a mesh), ``gdn_out`` (each head's output through ONE RMSNorm
weight of its value size, times SiLU of the gate; the output projection;
the add).

The layer checkpoint keeps the input projection's output (``gdn_proj``:
bf16, 142 MB a layer at 4096 tokens of the published 17340 columns).  The
step reports ``gdn_state_absmax``, the largest state any layer saw at a
chunk's end (under a mesh the largest over the shards).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, a_log, conv, dt_bias, fold, ones, residual_out)
from ray_tpu.models.blocks.residual import add, block_in, out_norm
from ray_tpu.ops.delta import delta_chunked
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssm import causal_conv1d
from ray_tpu.parallel.sharding import batch_shard_map

SAVED = ("gdn_proj",)
GDN_STATE_ABSMAX = "gdn_state_absmax"
STATS = {GDN_STATE_ABSMAX: "max"}


def _shapes(cfg):
    """``gdn_in`` gives [q | k | v | gate | a | b] side by side (``a`` the
    decay's input and ``b`` the write strength's, a number a head each);
    the convolution runs over q, k and v; ``gdn_gate_norm`` is ONE weight
    of a head's value size.  ``gdn_inner`` maps to no mesh axis: a tp split
    has to cut each part of the one projection (later)."""
    d, keys, values = cfg.embed_dim, cfg.gdn_key_inner, cfg.gdn_value_inner
    return {
        "gdn_norm": Param((d,), ("layer", "embed"), ones),
        "gdn_in": Param((d, 2 * keys + 2 * values + 2 * cfg.gdn_heads),
                        ("layer", "kernel_in", "gdn_inner")),
        "gdn_conv_w": Param((cfg.gdn_conv, cfg.gdn_conv_dim),
                            ("layer", None, "gdn_inner"), conv(cfg.gdn_conv)),
        "gdn_dt_bias": Param((cfg.gdn_heads,), ("layer", None), dt_bias),
        "gdn_A_log": Param((cfg.gdn_heads,), ("layer", None), a_log),
        "gdn_gate_norm": Param((cfg.gdn_value_dim,), ("layer", None), ones),
        "gdn_out": Param((values, d), ("layer", "gdn_inner", "kernel_in"),
                         residual_out(cfg)),
    }


def shard_rule(q, k, v, g, beta):
    """The rule as one shard of the batch runs it (one device: the whole
    batch): ``(o, the largest state at a chunk's end)``."""
    o, _, peak = delta_chunked(q, k, v, g, beta)
    return o, peak


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True):
    cfg, mesh = ctx.cfg, ctx.mesh
    b, s = x.shape[0], x.shape[1]
    heads, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    keys, values = cfg.gdn_key_inner, cfg.gdn_value_inner
    f32 = jnp.float32
    with jax.named_scope("gdn_in"):
        h = block_in(x, lp["gdn_norm"], cfg)
        proj = checkpoint_name(h @ lp["gdn_in"].astype(cfg.dtype), *SAVED)
        qkv, gate, a, bt = jnp.split(
            proj, [cfg.gdn_conv_dim, cfg.gdn_conv_dim + values,
                   cfg.gdn_conv_dim + values + heads], -1)
    with jax.named_scope("gdn_conv"):
        # the rule reads (b, heads, d, s): the mixer stands tokens-last
        conv = functools.partial(causal_conv1d, tokens_last=True)
        if mesh is not None and not ctx.sp_manual:
            conv = batch_shard_map(conv, mesh, (3, None), 3)
        qkv = conv(qkv, lp["gdn_conv_w"])
        q, k, v = jnp.split(qkv, [keys, 2 * keys], -1)

        def unit(t):  # each head's vector at length 1, float32
            t = t.reshape(b, s, heads, dk).astype(f32)
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q = (unit(q) * dk ** -0.5).astype(cfg.dtype)
        k = unit(k).astype(cfg.dtype)
        beta = jax.nn.sigmoid(bt.astype(f32))
        if cfg.gdn_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(lp["gdn_A_log"].astype(f32)) * jax.nn.softplus(
            a.astype(f32) + lp["gdn_dt_bias"].astype(f32))
    with jax.named_scope("gdn_scan"):
        rule = shard_rule
        if mesh is not None and not ctx.sp_manual:
            rule = batch_shard_map(shard_rule, mesh, (4, 4, 4, 3, 3), (4, None),
                                   reduce=jax.lax.pmax)
        o, peak = rule(q, k, v.reshape(b, s, heads, dv), g, beta)
    with jax.named_scope("gdn_out"):
        o = (rms_norm(o.astype(f32), lp["gdn_gate_norm"], cfg.norm_eps)
             * jax.nn.silu(gate.reshape(b, s, heads, dv).astype(f32))
             ).astype(cfg.dtype)
        return add(ctx, x, o.reshape(b, s, values) @ lp["gdn_out"].astype(
            cfg.dtype), residual, out_norm(lp, "gdn", cfg)), fold(
                aux, {GDN_STATE_ABSMAX: peak}, STATS)


BLOCK = Block(_shapes, _apply, saved=SAVED,
              scopes=("gdn_in", "gdn_conv", "gdn_scan", "gdn_out"),
              stats=lambda cfg: STATS)
