"""A gated short-convolution mixer (``layer_types``: ``conv``;
``ops/ssm.py::gated_short_conv``), as in LFM2's layers beside attention
ones with a QK-norm over each head: ``C * conv(B * x)``, a causal depthwise
convolution of ``sconv_width`` taps a channel with no bias and no
activation between two elementwise gates.

Scopes: ``sconv_in`` (norm, the one projection), ``sconv_gate`` (the gated
convolution), ``sconv_out`` (the output projection, the add).  The block's
state is the convolution's tail alone, ``sconv_width - 1`` tokens;
elementwise and local in time, so under a mesh the partitioner splits it by
rows as it does a norm; not under a split of the sequence.  The layer
checkpoint keeps nothing of it.
"""

import jax

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, conv, ones, residual_out)
from ray_tpu.models.blocks.residual import add
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.ssm import gated_short_conv


def _shapes(cfg):
    """``sconv_in`` gives [B | C | x] side by side, each as wide as the
    model (the published layout of ``in_proj``).  ``sconv_inner`` maps to
    no mesh axis: a tp split has to cut each part (later)."""
    d = cfg.embed_dim
    return {
        "sconv_norm": Param((d,), ("layer", "embed"), ones),
        "sconv_in": Param((d, 3 * d), ("layer", "kernel_in", "sconv_inner")),
        "sconv_w": Param((cfg.sconv_width, d), ("layer", None, "sconv_inner"),
                         conv(cfg.sconv_width)),
        "sconv_out": Param((d, d), ("layer", "sconv_inner", "kernel_in"),
                           residual_out(cfg)),
    }


def _apply(ctx: Ctx, x, aux, lp, residual: bool = True):
    if ctx.sp_manual:
        raise NotImplementedError(
            "the short convolution needs the tail of the sequence shard "
            "before its own: not under a manual 'sp' region")
    cfg = ctx.cfg
    with jax.named_scope("sconv_in"):
        h = rms_norm(x, lp["sconv_norm"], cfg.norm_eps)
        bcx = ctx.cst(h @ lp["sconv_in"].astype(cfg.dtype),
                      ("batch", "seq", "sconv_inner"))
    with jax.named_scope("sconv_gate"):
        y = gated_short_conv(bcx, lp["sconv_w"])
    with jax.named_scope("sconv_out"):
        return add(ctx, x, y @ lp["sconv_out"].astype(cfg.dtype),
                   residual), aux


BLOCK = Block(_shapes, _apply,
              scopes=("sconv_in", "sconv_gate", "sconv_out"))
