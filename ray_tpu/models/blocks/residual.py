"""The residual a layer's blocks add to: one stream, or ``hc_mult`` streams
mixed round every block by learned doubly stochastic maps
(manifold-constrained hyper-connections, arXiv:2512.24880 §4).

The streams lie side by side, ``(b, s, n * d)``: stream j is the columns
j * d .. (j + 1) * d, so ``vec X`` is the array as it lies and every slice
starts on a lane tile.  (A (b, s, n, d) array would pad its n = 4 rows to a
16-row tile.)  Round every block they are read and written ONCE a pass
(``ops/streams.py``: the maps, the two halves and their written-out
backward passes, the kernels and what chooses them).  The layer checkpoint
keeps nothing of either half: the rematerialised forward runs
``streams_read`` again and, of a layer's two blocks, the first one's
``streams_write``.
"""

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks.base import (
    BIAS_STD, Ctx, Param, constant, keyed_ones, ones, small)
from ray_tpu.ops import streams
from ray_tpu.ops.layers import layer_norm, rms_norm

# The scopes the n-stream wrapper opens round a block.
SCOPES = ("hc_map", "hc_mix")


def scaled(x, multiplier: float):
    """``x * multiplier``; a multiplier of 1 adds no op to the program."""
    return x if multiplier == 1.0 else x * multiplier


def norm_shapes(cfg, name: str):
    """A block's norm ``<name>_norm`` — with its bias ``<name>_norm_bias``
    where the model's norms are LayerNorms (``norm_type``) — and, in a
    model that norms both what a block reads and what it adds
    (``block_norm="sandwich"``), the second one, ``<name>_post_norm``,
    whose gain starts at ``cfg.post_norm_init``."""
    axes = ("layer", "embed")
    shapes = {name + "_norm": Param((cfg.embed_dim,), axes, ones)}
    if cfg.norm_type == "layernorm":
        shapes[name + "_norm_bias"] = Param((cfg.embed_dim,), axes,
                                            small(BIAS_STD))
    if cfg.block_norm == "sandwich":
        shapes[name + "_post_norm"] = Param(
            (cfg.embed_dim,), axes, constant(cfg.post_norm_init))
    return shapes


def block_in(x, weight, cfg, bias=None):
    """What a block reads: the stream through the block's norm (a
    LayerNorm where the model gives it a ``bias``), or, in a model that
    norms ONLY what a block adds (``add``), the stream as it is."""
    if cfg.block_norm == "output":
        return x
    return norm(x, weight, bias, cfg.norm_eps)


def norm(x, weight, bias, eps: float):
    """The model's norm: RMSNorm, or with a ``bias`` LayerNorm."""
    if bias is None:
        return rms_norm(x, weight, eps)
    return layer_norm(x, weight, bias, eps)


def out_norm(lp, name: str, cfg):
    """The weight of the norm on what the block ``name`` adds, for ``add``:
    the block's one norm in a model that norms its output, its second in
    one that norms both sides, None in one that norms its input alone."""
    return {"input": None, "output": lp.get(name + "_norm"),
            "sandwich": lp.get(name + "_post_norm")}[cfg.block_norm]


def add(ctx: Ctx, x, y, residual: bool, weight=None):
    """What a block hands on: the stream plus its output ``y`` (inside
    the block's last scope), or ``y`` alone where the layer mixes it into
    several streams itself.  ``weight`` (``out_norm``): where the model
    norms what a block adds, that norm's, applied here."""
    cfg = ctx.cfg
    if weight is not None:
        y = rms_norm(y, weight, cfg.norm_eps)
    y = scaled(ctx.cst(y, ("batch", "seq", "embed")), cfg.residual_multiplier)
    return x + y if residual else y


def _maps_init(n: int):
    """The maps start NEAR the plain residual and not AT it (a comparison
    with a reference could not see a map that is the identity, nor a
    uniform one): a block reads about the streams' mean (pre:
    sigmoid(-ln(n - 1)) = 1 / n each), writes to every stream (post:
    2 sigmoid(0) = 1), and a stream mostly keeps itself (res: 4 on the
    diagonal before exp and Sinkhorn, 0.95 after), each bias with normal
    noise of 0.1 on it; the three scales are 1, so the part that depends
    on the token is of the order of the bias."""
    def init(key, shape):
        static = jnp.concatenate([
            jnp.full((n,), -jnp.log(n - 1.0)), jnp.zeros((n,)),
            4.0 * jnp.eye(n).reshape(-1)])
        return static + 0.1 * jax.random.normal(key, shape, jnp.float32)
    return init


def shapes(cfg):
    """The maps of the n-stream residual, a set for each of a layer's two
    blocks: one projection of the normed streams to [pre (n) | post (n) |
    res (n x n, row-major)], its bias, and the three scales."""
    n = cfg.hc_mult
    if n == 1:
        return {}
    maps = 2 * n + n * n
    out = {}
    for block in ("attn", "ffn"):
        out.update({
            f"hc_{block}_proj": Param((n * cfg.embed_dim, maps),
                                      ("layer", None, None)),
            f"hc_{block}_bias": Param((maps,), ("layer", None),
                                      _maps_init(n)),
            f"hc_{block}_scale": Param((3,), ("layer", None), keyed_ones)})
    return out


def to_streams(x, cfg):
    """The embedded tokens copied to every stream (arXiv:2409.19606 §3)."""
    return x if cfg.hc_mult == 1 else jnp.tile(x, (1, 1, cfg.hc_mult))


def from_streams(xs, cfg):
    """The streams summed, for the last norm (arXiv:2409.19606 §3)."""
    if cfg.hc_mult == 1:
        return xs
    d = xs.shape[-1] // cfg.hc_mult
    with jax.named_scope("hc_mix"):
        return sum(xs[..., j * d:(j + 1) * d].astype(jnp.float32)
                   for j in range(cfg.hc_mult)).astype(cfg.dtype)


def hc_block(ctx: Ctx, xs, lp, block: str, fn):
    """One block ``fn(x) -> (y, rest)`` on the streams ``xs``: ``X' = res
    X + post^T fn(pre X)``; returns ``(X', rest)``.  Before the block
    (scope ``hc_map``, ``streams.streams_read``) the token's maps and the
    block's input ``x = pre X``; after it (scope ``hc_mix``,
    ``streams.streams_write``) the write back.  The block opens its own
    scopes between them.  The form follows the shapes — but under a mesh
    or inside a manual region it is the XLA one whatever they are: the
    streams' kernels take one chip's whole arrays."""
    cfg = ctx.cfg
    plan = streams.plan_for(
        xs, cfg.hc_mult, norm_eps=cfg.norm_eps,
        clamp=(cfg.hc_clamp_min, cfg.hc_clamp_max),
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        form=None if ctx.mesh is None and not ctx.sp_manual else "xla")
    with jax.named_scope("hc_map"):
        x, maps, xs = streams.streams_read(
            plan, xs, lp[f"hc_{block}_proj"], lp[f"hc_{block}_scale"],
            lp[f"hc_{block}_bias"])
    y, rest = fn(x)
    with jax.named_scope("hc_mix"):
        return streams.streams_write(plan, xs, y, maps), rest
