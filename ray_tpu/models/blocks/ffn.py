"""The FFNs: a dense one (scope ``ffn``), and the dropless expert layer
(``ops/moe.py``, under its scopes ``moe_route`` / ``moe_dispatch`` /
``moe_experts`` / ``moe_combine`` in place of ``ffn``, and ``moe_exchange``
where its experts are spread over an ``ep`` axis) with or without a
shared expert, which every token meets (scope ``ffn``), and with or
without a LATENT the routed experts work in (``cfg.moe_latent``: ``w_latent_in
(d, l)`` projects the normed stream down before the dispatch — and before
the exchange, which then carries ``l``-wide rows —, the held experts'
matrices have ``l`` rows in place of ``d``, and ``w_latent_out (l, d)`` brings
each token's summed parts back up after the combine; both under the scope
``moe_latent``; the router and the shared expert read the normed stream,
never the latent).  Every FFN of a
model — dense, routed, shared — has the model's one activation
(``cfg.ffn_act``): SwiGLU over a gate and an up matrix, or the ungated
``relu(h W_up) ** 2 W_down`` of two matrices.  Expert tensors are
sharded over 'ep' and so are the tokens: inside a shard_map the layer's
exchange (scope ``moe_exchange``) brings each rank the tokens of its group,
the rank computes its own experts' rows, and each token's parts come back
summed to the rank that owns it.  One chip's share of a layer
(``experts_held``, ``first_expert``): the router keeps its published width,
the expert tensors hold the experts that live here, and what the absent
ones would add is left out.  The layer checkpoint keeps the row index
(``ops.moe.SAVED_RESIDUALS``: no sort under ``rematted_computation``; the
row gather runs again).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.blocks.base import (
    Block, Ctx, Param, fold, normal, residual_out)
from ray_tpu.models.blocks.residual import (
    add, block_in, norm_shapes, out_norm)
from ray_tpu.ops import moe
from ray_tpu.ops.layers import rms_norm, swiglu
from ray_tpu.parallel.mesh import (
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_SP, AXIS_TP)
from ray_tpu.parallel.sharding import BATCH_AXES, manual_shard_map


def _gated(cfg) -> bool:
    return cfg.ffn_act == "swiglu"


def _ffn_shapes(cfg, m: int, prefix: str = "w_", *held, latent: int = 0):
    """An FFN of width ``m`` (``held`` experts of it): gate, up and down,
    or up and down alone where the activation has no gate.  In a ``latent``
    it reads and writes that width, and its down matrix — which then does
    not write to the residual — is drawn as any other."""
    d = latent or cfg.embed_dim
    expert = ("expert",) * len(held)
    up = Param((*held, d, m), ("layer", *expert, "kernel_in", "mlp"))
    down = Param((*held, m, d), ("layer", *expert, "mlp", "kernel_in"),
                 normal if latent else residual_out(cfg))
    gate = {prefix + "gate": up} if _gated(cfg) else {}
    return {**gate, prefix + "up": up, prefix + "down": down}


def _dense_shapes(cfg):
    return {**norm_shapes(cfg, "mlp"), **_ffn_shapes(cfg, cfg.dense_width)}


def _select_bias(std: float):
    """Drawn off zero (``cfg.select_bias_init``) so that a comparison with
    a reference can see it (a trained one starts at 0)."""
    return lambda key, shape: std * jax.random.normal(key, shape, jnp.float32)


def _moe_shapes(cfg):
    """The router over ALL the experts, the tensors of those held here
    (``mlp_dim`` is an expert's width), the selection bias (float32
    whatever the parameters': it moves by thousandths) and the shared
    expert where the model has them; round the held experts the latent
    pair where they work in one (``w_latent_out`` is the one that writes to
    the residual: the initialiser's scale is its, not the experts')."""
    d, e, latent = cfg.embed_dim, cfg.num_experts, cfg.moe_latent
    shapes = {
        **norm_shapes(cfg, "mlp"),
        "router": Param((d, e), ("layer", "kernel_in", None)),
        **_ffn_shapes(cfg, cfg.mlp_dim, "w_", cfg.local_experts,
                      latent=latent),
    }
    if latent:
        shapes["w_latent_in"] = Param((d, latent),
                                      ("layer", "kernel_in", None))
        shapes["w_latent_out"] = Param((latent, d),
                                       ("layer", None, "kernel_in"),
                                       residual_out(cfg))
    if cfg.select_bias:
        shapes["router_bias"] = Param(
            (e,), ("layer", None), _select_bias(cfg.select_bias_init),
            jnp.float32)
    if cfg.shared_experts:
        shapes.update(_ffn_shapes(cfg, cfg.shared_width, "shared_"))
    return shapes


def _moe_stats(cfg):
    """``ops.moe``'s statistics under the names the step reports them,
    ``moe_`` before its own but for the two losses; ``moe_held_share`` (how
    much of the rows is here) only where the layer holds a share."""
    held = {"moe_held_share": "mean"} if cfg.experts_held else {}
    return {"aux_loss": "mean", "z_loss": "mean",
            "moe_load_max_over_mean": "max", "moe_dropped": "sum",
            "moe_rows_visited_share": "mean",
            "moe_token_rows_read_share": "mean",
            "moe_rank_rows_max_over_mean": "max", **held}


def _ffn(h, lp, cfg, prefix: str = "w_"):
    w = lambda name: lp[prefix + name].astype(cfg.dtype)  # noqa: E731
    hidden = (swiglu(h @ w("gate"), h @ w("up")) if _gated(cfg)
              else jnp.square(jax.nn.relu(h @ w("up"))))
    return hidden @ w("down")


def _dense_ffn(ctx: Ctx, x, aux, lp, residual: bool = True):
    """-> (the stream, aux, nothing handed out of the scan)."""
    cfg = ctx.cfg
    with jax.named_scope("ffn"):
        h = block_in(x, lp["mlp_norm"], cfg, lp.get("mlp_norm_bias"))
        return add(ctx, x, _ffn(h, lp, cfg), residual,
                   out_norm(lp, "mlp", cfg)), aux, None


def _moe(ctx: Ctx, x, lp, residual: bool = True):
    """The expert layer (``ops.moe.moe_block``) on the residual stream
    (its experts' sum alone without ``residual``).
    Under a mesh it runs per shard, as the flash kernel does: tokens over
    (dp, fsdp, ep) x sp, experts over ep, their width over tp; the layer
    gathers its group's tokens over ep, and the partial outputs are
    scattered back over ep and summed over tp.  Inside a region that is
    already manual (the pipeline) it is called as it is and the partitioner
    splits it, which the TPU lowering refuses for a Mosaic kernel."""
    cfg, mesh, cst = ctx.cfg, ctx.mesh, ctx.cst
    # the experts' matrices, in ``moe_block``'s order; no gate: None there
    names = ("w_gate", "w_up", "w_down")[not _gated(cfg):]

    no_gate = (None,) * (not _gated(cfg))
    latent = ("w_latent_in", "w_latent_out") if cfg.moe_latent else ()

    def moe_block(x, norm_w, router_w, *rest, **axes):  # the region's name
        n = len(rest) - len(latent)
        return moe.moe_block(
            x, norm_w, router_w, *no_gate, *rest[:n], latent=rest[n:] or None,
            num_selected=cfg.num_selected, norm_eps=cfg.norm_eps,
            norm_topk_prob=cfg.norm_topk_prob,
            topk_norm_eps=cfg.topk_norm_eps, scoring=cfg.router_scoring,
            gate_scale=cfg.routed_scaling_factor,
            first_expert=cfg.first_expert, residual=residual, **axes)

    bias = (lp["router_bias"],) if cfg.select_bias else ()
    args = (x, lp["mlp_norm"], lp["router"],
            *(lp[name] for name in names)) + bias + tuple(
                lp[name] for name in latent)
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return moe_block(*args)
    # The parameters as the region takes them, laid out under the scope
    # that uses them (pinned first as they are stored, or the partitioner
    # moves the change of layout up to the scan's slice): the fsdp gathers
    # and their gradients' scatters then carry a step scope like every
    # other collective.
    stored = _moe_shapes(cfg)

    def laid_out(name, *gathered):
        return cst(cst(lp[name], stored[name].axes[1:]), gathered)

    with jax.named_scope("moe_route"):
        small = (laid_out("mlp_norm", None), laid_out("router", None, None))
    up_axes, down_axes = ("expert", None, "mlp"), ("expert", "mlp", None)
    with jax.named_scope("moe_experts"):
        args = (x,) + small + tuple(
            laid_out(name, *(down_axes if name == "w_down" else up_axes))
            for name in names) + bias
    if latent:
        with jax.named_scope("moe_latent"):
            args += tuple(laid_out(name, None, None) for name in latent)
    x_spec = P(BATCH_AXES, AXIS_SP, None)
    up_spec = P(AXIS_EP, None, AXIS_TP)
    fn = manual_shard_map(
        functools.partial(moe_block, token_axes=(AXIS_DP, AXIS_FSDP, AXIS_SP),
                          expert_axis=AXIS_EP, sum_axes=(AXIS_TP,)),
        set(mesh.axis_names),
        in_specs=(x_spec, P(), P()) + (up_spec,) * (len(names) - 1)
        + (P(AXIS_EP, AXIS_TP, None),) + (P(),) * len(bias + latent),
        out_specs=(x_spec, P()), mesh=mesh)
    return fn(*args)


def _moe_ffn(ctx: Ctx, x, aux, lp, residual: bool = True):
    """The expert layer and, where the model has one, the shared expert.
    Hands the experts' assignments out of the scan where a selection bias
    is moved by them.  In a model that norms what a block adds too
    (``block_norm="sandwich"``; the layer norms its input inside, scope
    ``moe_route``) the experts' sum PLUS the shared expert's output goes
    through that norm, and then onto the stream (scope ``moe_combine``)."""
    cfg, cst = ctx.cfg, ctx.cst
    post = out_norm(lp, "mlp", cfg)
    out, seen = _moe(ctx, x, lp, residual and post is None)
    out = cst(out, ("batch", "seq", "embed"))
    if cfg.shared_experts:
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            out = out + cst(_ffn(h, lp, cfg, "shared_"),
                            ("batch", "seq", "embed"))
    if post is not None:
        with jax.named_scope("moe_combine"):
            out = add(ctx, x, out, residual, post)
    how = _moe_stats(cfg)
    aux = fold(aux, {k: seen[k.removeprefix("moe_")] for k in how}, how)
    return out, aux, seen["counts"] if cfg.select_bias else None


DENSE = Block(_dense_shapes, _dense_ffn, scopes=("ffn",))
MOE = Block(_moe_shapes, _moe_ffn, saved=moe.SAVED_RESIDUALS,
            scopes=("moe_route", "moe_exchange", "moe_dispatch",
                    "moe_experts", "moe_combine", "moe_latent", "ffn"),
            stats=_moe_stats)
