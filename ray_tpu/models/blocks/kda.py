"""A delta-rule linear-attention mixer whose decay is a VECTOR over the key
channels (Kimi Delta Attention, arXiv:2510.26692; ``ops/delta.py``'s
``kda_chunked``), the mixer ``kda`` of a model whose ``linear_attn_config``
lists its layers, or whose ``gqa_layers`` leaves them over
(``LlamaConfig.layer_kinds``), beside latent- or softmax-attention ones.
Scopes: ``kda_in`` (the block's norm and the ONE input projection,
[q | k | v | the decay's down-projection | the gate's | b] side by side),
``kda_conv`` (the convolution over q, k, v with its SiLU — the kernels
``causal_conv_fwd`` / ``causal_conv_bwd`` where ``ssm.conv_kernels_fit``,
per shard of the batch under a mesh —, the L2 norm of
each head's q and k — q then times ``head_dim ** -0.5`` —, ``beta =
sigmoid(b)``, TWICE that under ``cfg.kda_neg_eigval`` (the public files'
``kda_allow_neg_eigval``: a write strength in (0, 2), the transition's
eigenvalues in (-1, 1)), and the log-decay a key channel ``g =
-exp(A_log_head) softplus(up(down) + dt_bias)``), ``kda_scan`` (the chunked
rule, per shard of the batch under a mesh), ``kda_out`` (each head's output
through ONE RMSNorm weight of its size, times the SIGMOID of the low-rank
gate; the output projection; the add).

The layer checkpoint keeps the input projection's output (``kda_proj``:
bf16, 206 MB a layer at 8192 tokens of the published 12576 columns) and,
where the rule runs as its Pallas pair, what ``kdarule_bwd`` and ``kda_out``
read of the forward pass (``ops.delta.KDA_SAVED_RESIDUALS``, named inside
the rule's ``custom_vjp``: the kernel's output and each pair's inverse,
bf16, 67 MB a layer each at 32 heads x 8192 tokens, and the float32 state
entering each grid step of 8 chunks, 33.5 MB — 168 MB a layer), so that the
rematerialised pass holds no second ``kdarule_fwd``; the XLA form makes no
such name and keeps nothing of the rule.  The step reports ``kda_state_absmax``, the largest
state any layer saw at a chunk's end, and ``kda_chunk_decay_min``, the most
negative cumulative log-decay inside a chunk (under -88 a factored chunk
matrix would have overflowed: the rule's levels are what keeps it exact),
and a model with ``kda_neg_eigval`` ``kda_beta_max`` too, the largest write
strength of the step (over 1: the negative-eigenvalue path ran).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, a_log, conv, dt_bias, fold, ones, residual_out)
from ray_tpu.models.blocks.residual import add, block_in, out_norm
from ray_tpu.ops.delta import KDA_SAVED_RESIDUALS, kda_chunked
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssm import causal_conv1d
from ray_tpu.parallel.sharding import batch_shard_map

KDA_PROJ = "kda_proj"
SAVED = (KDA_PROJ, *KDA_SAVED_RESIDUALS)
KDA_STATE_ABSMAX = "kda_state_absmax"
KDA_CHUNK_DECAY_MIN = "kda_chunk_decay_min"
KDA_BETA_MAX = "kda_beta_max"
STATS = {KDA_STATE_ABSMAX: "max", KDA_CHUNK_DECAY_MIN: "min"}
L2_EPS = 1e-6
_beta = jax.nn.sigmoid      # the write strength, in (0, 1)
_gate = jax.nn.sigmoid      # the output gate (Gated DeltaNet's is a SiLU)


def _shapes(cfg):
    """``kda_in`` gives [q | k | v | f | gate | b] (``f`` and ``gate`` the
    ``kda_rank`` columns the decay and the output gate come up from, ``b``
    the write strength's number a head); the convolution runs over q, k and
    v; ``kda_A_log`` is a number a head, ``kda_dt_bias`` one a key channel;
    ``kda_gate_norm`` ONE weight of a head's size.  ``kda_inner`` maps to no
    mesh axis, as the delta-rule mixer's."""
    d, inner, rank = cfg.embed_dim, cfg.kda_inner, cfg.kda_rank
    return {
        "kda_norm": Param((d,), ("layer", "embed"), ones),
        "kda_in": Param((d, 3 * inner + 2 * rank + cfg.kda_heads),
                        ("layer", "kernel_in", "kda_inner")),
        "kda_conv_w": Param((cfg.kda_conv, 3 * inner),
                            ("layer", None, "kda_inner"), conv(cfg.kda_conv)),
        "kda_f_up": Param((rank, inner), ("layer", None, "kda_inner")),
        "kda_dt_bias": Param((inner,), ("layer", None), dt_bias),
        "kda_A_log": Param((cfg.kda_heads,), ("layer", None), a_log),
        "kda_g_up": Param((rank, inner), ("layer", None, "kda_inner")),
        "kda_gate_norm": Param((cfg.kda_head_dim,), ("layer", None), ones),
        "kda_out": Param((inner, d), ("layer", "kda_inner", "kernel_in"),
                         residual_out(cfg)),
    }


def _stats(cfg):
    """The statistics a layer folds: ``kda_beta_max`` only where the write
    strength can pass 1."""
    return {**STATS, KDA_BETA_MAX: "max"} if cfg.kda_neg_eigval else STATS


def shard_rule(q, k, v, g, beta):
    """The rule as one shard of the batch runs it: ``(o, the largest state
    at a chunk's end, the NEGATIVE of the most negative cumulative
    log-decay inside a chunk)`` — shards join both by their maximum."""
    o, _, peak, decay_min = kda_chunked(q, k, v, g, beta)
    return o, peak, -decay_min


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True):
    cfg, mesh = ctx.cfg, ctx.mesh
    b, s = x.shape[0], x.shape[1]
    heads, dh, inner, rank = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner,
                              cfg.kda_rank)
    f32 = jnp.float32
    with jax.named_scope("kda_in"):
        h = block_in(x, lp["kda_norm"], cfg)
        proj = checkpoint_name(h @ lp["kda_in"].astype(cfg.dtype), KDA_PROJ)
        qkv, f, gate, bt = jnp.split(
            proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank], -1)
    with jax.named_scope("kda_conv"):
        # the rule reads (b, heads, d, s): the mixer stands tokens-last
        conv = functools.partial(causal_conv1d, tokens_last=True)
        if mesh is not None and not ctx.sp_manual:
            conv = batch_shard_map(conv, mesh, (3, None), 3)
        qkv = conv(qkv, lp["kda_conv_w"])
        q, k, v = jnp.split(qkv, 3, -1)

        def unit(t):  # each head's vector at length 1, float32
            t = t.reshape(b, s, heads, dh).astype(f32)
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

        q = (unit(q) * dh ** -0.5).astype(cfg.dtype)
        k = unit(k).astype(cfg.dtype)
        beta = _beta(bt.astype(f32))
        seen = {}
        if cfg.kda_neg_eigval:
            beta = 2.0 * beta
            seen[KDA_BETA_MAX] = jnp.max(beta)
        g = (f @ lp["kda_f_up"].astype(cfg.dtype)).astype(f32)
        g = -jnp.exp(lp["kda_A_log"].astype(f32))[:, None] * jax.nn.softplus(
            (g + lp["kda_dt_bias"].astype(f32)).reshape(b, s, heads, dh))
    with jax.named_scope("kda_scan"):
        rule = shard_rule
        if mesh is not None and not ctx.sp_manual:
            rule = batch_shard_map(shard_rule, mesh, (4, 4, 4, 4, 3),
                                   (4, None, None), reduce=jax.lax.pmax)
        o, peak, decay = rule(q, k, v.reshape(b, s, heads, dh), g, beta)
    with jax.named_scope("kda_out"):
        gate = (gate @ lp["kda_g_up"].astype(cfg.dtype)).reshape(
            b, s, heads, dh)
        o = (rms_norm(o.astype(f32), lp["kda_gate_norm"], cfg.norm_eps)
             * _gate(gate.astype(f32))).astype(cfg.dtype)
        return add(ctx, x, o.reshape(b, s, inner) @ lp["kda_out"].astype(
            cfg.dtype), residual, out_norm(lp, "kda", cfg)), fold(
                aux, {**seen, KDA_STATE_ABSMAX: peak,
                      KDA_CHUNK_DECAY_MIN: -decay}, _stats(cfg))


BLOCK = Block(_shapes, _apply, saved=SAVED,
              scopes=("kda_in", "kda_conv", "kda_scan", "kda_out"),
              stats=_stats)
