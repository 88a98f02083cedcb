"""A gated memory unit (``gmu``; arXiv:2507.06607 §2): the mixer of the
SambaY decoder's second half that holds no scan and no state of its own.
It READS ``s6_memory``, the scan output ``m`` (with ``D x``, before the
gate) that the nearest earlier Mamba-1 layer published
(``blocks/mamba1.py``), of the same tokens, and gates it by the layer's
own normed input ``h``:

    y = (m silu(h W_1)) W_2

``W_1 (embed_dim, inner)``, ``W_2 (inner, embed_dim)``, no bias.  One scope,
``gmu``; elementwise and local in time, so under a mesh the partitioner
splits it by rows.  The layer checkpoint keeps nothing of it.
"""

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks.base import Block, Ctx, Param, residual_out
from ray_tpu.models.blocks.mamba1 import MEMORY
from ray_tpu.models.blocks.residual import add, block_in, norm_shapes


def _shapes(cfg):
    d, inner = cfg.embed_dim, cfg.s6_inner
    return {
        **norm_shapes(cfg, "gmu"),
        "gmu_in": Param((d, inner), ("layer", "kernel_in", "ssm_inner")),
        "gmu_out": Param((inner, d), ("layer", "ssm_inner", "kernel_in"),
                         residual_out(cfg)),
    }


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True, *, shared):
    cfg, f32 = ctx.cfg, jnp.float32
    with jax.named_scope("gmu"):
        h = block_in(x, lp["gmu_norm"], cfg, lp.get("gmu_norm_bias"))
        gate = jax.nn.silu((h @ lp["gmu_in"].astype(cfg.dtype)).astype(f32))
        y = (shared[MEMORY].astype(f32) * gate).astype(cfg.dtype)
        return add(ctx, x, y @ lp["gmu_out"].astype(cfg.dtype),
                   residual), aux


BLOCK = Block(_shapes, _apply, scopes=("gmu",), reads=(MEMORY,))
