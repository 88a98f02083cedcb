"""The softmax mixers: attention (``layer_types``: ``attention`` or, as
the OLMo and LFM2 files spell it, ``full_attention``; ``sliding_attention``,
as the ``afmoe`` file spells it, is the same mixer under the model's
``sliding_window``: a query sees itself and the ``window - 1`` tokens before
it, the flash kernels skip what lies beyond either edge, and the layer
reports ``attn_window_executed_share`` and
``attn_window_masked_tile_share``) and latent attention
(arXiv:2412.19437 §2.1.1: q and k/v come up from normed low-rank
projections, a head's q and k are [no-position part | rotary part, the k's
shared by all heads] and wider than its v; the flash kernels take the two
head sizes).  The attention itself is pluggable (``cfg.attn_impl``): pallas
flash (``ops/attention.py``), ring over 'sp', Ulysses all-to-all, or the
XLA reference — all numerically interchangeable (tested).  ``indexed`` is
the softmax mixer of a model with an ``sa_config``: the same q, k and v, and
beside them an INDEXER on the same normed input, detached, whose scores pick
the ``topk`` keys a query reads (``ops/sparse_attention.py``: scopes
``dsa_index``, ``dsa_select``, the softmax over the picked keys under
``attention``, the indexer's own loss under ``dsa_loss``; ``INDEX_STATS``
ride out of the scan).

All open the scopes ``attn_qkv`` (norm, projections, RoPE), ``attention``
and ``attn_out`` (``wo`` and the add), and the layer checkpoint keeps the
flash kernel's output and log-sum-exp (``ops.attention.SAVED_RESIDUALS``:
no ``flash_fwd`` under ``rematted_computation``).  Which layers rotate q
and k is the configuration's to say (``cfg.rotary``), and by which tables
— one rule a model, or one a KIND of layer (``cfg.rope_rule``; scope
``rope`` inside ``attn_qkv``: ``_rope_tables``, for both mixers).  The
softmax mixer rotates q and k where the projections leave them, ``(b, s,
heads x d)`` — the layout the flash kernels read a head of 128 lanes in —
wherever what it can see allows (``_rotates_flat``: a head of whole lane
blocks, no per-head norm, no 'tp' over the lanes), by ONE small kernel
(``ops/rotary.py``: the tables' ``(rows, d)`` block in VMEM serves every
head of a row tile, a lane rotate swaps a head's halves, the flash
kernels' pre-scale of q is its epilogue), else on the 4-D view by
``apply_rope``, as the latent mixer rotates its 64-wide rotary part.  A
kind of layer that rotates a SHARE of a head (``cfg.rotary_dim``: the
first ``r`` of a head's dimensions, the tables reckoned over ``r``, the rest
passing through untouched) goes by the 4-D view too, under the scope
``rope_partial`` inside ``rope``.  How many QUERY heads a layer has is its
kind's as well (``cfg.q_heads``: ``wq``, ``wo`` and the gate of a
``sliding_attention`` run may be wider or narrower than a full run's; the
KV heads are the model's).  With
``attn_output_gate`` a fourth projection ``wg`` of the block's input
(scope ``attn_qkv``) gates the heads' outputs, ``o * sigmoid(g)``, before
``wo`` (scope ``attn_out``) — a gate a head and channel (``wg`` as wide as
``wq``), or ONE number a head (``"per_head"``: ``wg (d, heads)``, the
product under the scope ``attn_head_gate`` inside ``attn_out``); the
checkpoint keeps nothing of it: the rematerialised forward computes ``g``
with q, k and v.

DIFFERENTIAL attention (arXiv:2410.05258; ``diff_sliding``, ``diff_full``,
``diff_cross``: the attention layers of a SambaY decoder, arXiv:2507.06607)
stands behind them: heads in pairs, two softmaxes over one doubled value
head, their difference under a learned lambda (``_diff_mixer``, scope
``attn_diff``).  ``diff_full`` PUBLISHES its keys and values; ``diff_cross``
holds no key or value projection and READS them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.blocks.base import (
    BIAS_STD, Block, Ctx, Param, constant, fold, ones, residual_out, small)
from ray_tpu.models.blocks.residual import (
    add, block_in, norm_shapes, out_norm)
from ray_tpu.ops import attention, rotary, sparse_attention
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.layers import (
    apply_rope, layer_norm as _layer_norm, repeat_kv_heads, rms_norm,
    scaled_rope, yarn_mscale)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.parallel.mesh import AXIS_SP, AXIS_TP
from ray_tpu.parallel.sharding import (
    BATCH_AXES, batch_shard_map, manual_shard_map)

SCOPES = ("attn_qkv", "attention", "attn_out")
# A windowed layer's statistics: the (q, k) pairs its attention computes
# over the pairs its window leaves (1.0: no masked pair computed), and of
# the sub-tiles it executes the share that lies on an edge and takes a mask
# (1.0 where no flash kernel runs: the XLA form masks the whole square).
WINDOW_EXECUTED = "attn_window_executed_share"
WINDOW_MASKED = "attn_window_masked_tile_share"
WINDOW_STATS = {WINDOW_EXECUTED: "max", WINDOW_MASKED: "max"}
# Of a model whose head count follows the layer's kind
# (``cfg.heads_per_layer``): the query heads a full and a windowed layer
# ran (read off the q the layer made), the dimensions of a head a full layer
# rotated (the width ``_rotated`` read) and the keys a windowed layer's
# query saw at most (the window its attention was handed: the sequence's
# length where the window cuts nothing off).
Q_HEADS = {False: "attn_q_heads_full", True: "attn_q_heads_window"}
ROTARY_WIDTH_FULL = "attn_rotary_width_full"
WINDOW_KEYS = "attn_window_keys"
# An indexed layer's: the indexer's own loss (the layers' mean joins the
# step's), the (q, k) pairs its selection holds over the causal ones,
# counted from the mask it made, by how many pairs that count is off
# what ``topk`` keys a query give (0: neither more nor fewer), and the share
# of the selection kernel's row blocks in which a tie at the threshold bound
# and was walked (0 where no kernel runs).
INDEX_SCOPES = ("attn_qkv", "dsa_index", "dsa_select", "attention",
                "dsa_loss", "attn_out")
INDEX_LOSS = "idx_loss"
SELECTED_SHARE = "dsa_selected_share"
SELECTED_OFF = "dsa_selected_off"
TIE_WALK_SHARE = "dsa_tie_walk_share"
INDEX_STATS = {INDEX_LOSS: "mean", SELECTED_SHARE: "mean",
               SELECTED_OFF: "sum", TIE_WALK_SHARE: "mean"}
# A block-diffusion layer's: the (q, k) pairs its attention computes over
# the pairs the block rule needs (the whole square of both streams where no
# flash kernel runs), and the pairs on which the kernels' schedule and the
# rule's four cases disagree over one strip of rows (0: none).
BLOCK_EXECUTED = "attn_bd_executed_share"
BLOCK_MASK_OFF = "bd_mask_off"
BLOCK_STATS = {BLOCK_EXECUTED: "max", BLOCK_MASK_OFF: "sum"}


def _attention_shapes(cfg, windowed: bool = False):
    """A softmax layer's tensors; the query heads are its KIND's
    (``cfg.q_heads``)."""
    heads = cfg.q_heads(windowed)
    d, h, kvd = cfg.embed_dim, heads * cfg.head_dim, cfg.kv_dim
    shapes = {
        **norm_shapes(cfg, "attn"),
        "wq": Param((d, h), ("layer", "kernel_in", "heads")),
        "wk": Param((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wv": Param((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wo": Param((h, d), ("layer", "heads", "kernel_in"),
                    residual_out(cfg)),
    }
    if cfg.qk_norm:  # over the whole projection, before heads and RoPE
        shapes.update({"q_norm": Param((h,), ("layer", "heads"), ones),
                       "k_norm": Param((kvd,), ("layer", "kv_heads"), ones)})
    if cfg.qk_head_norm:  # over each head, ONE weight of a head's size
        head = Param((cfg.head_dim,), ("layer", "head_dim"), ones)
        shapes.update({"q_norm": head, "k_norm": head})
    if cfg.attn_output_gate:  # a gate a head and channel of the output,
        # or one number a head
        shapes["wg"] = Param(
            (d, heads if cfg.attn_output_gate == "per_head" else h),
            ("layer", "kernel_in", "heads"))
    return shapes


def _kind_stats(windowed: bool):
    """``Block.stats`` of the softmax mixer of this kind: a windowed
    layer's ``WINDOW_STATS``, and in a model whose head count follows the
    kind (nothing in any other) the kind's own counters."""
    own = {Q_HEADS[windowed]: "max",
           (WINDOW_KEYS if windowed else ROTARY_WIDTH_FULL): "max"}
    return lambda cfg: {**(WINDOW_STATS if windowed else {}),
                        **(own if cfg.heads_per_layer else {})}


def _latent_shapes(cfg):
    """``wq_a``/``wq_b`` take q down to ``q_lora_rank`` and up to heads x
    [nope | rope] — or, in a model without a q rank (the public
    ``q_lora_rank`` null), ONE matrix ``wq`` gives it; ``wkv_a`` gives
    [the latent c_kv | the one rotary k every head shares], ``wkv_b`` takes the normed latent up to heads x [k_nope |
    v] (the published layouts of ``kv_a_proj_with_mqa`` and
    ``kv_b_proj``)."""
    d, heads, qk = cfg.embed_dim, cfg.num_heads, cfg.latent_qk_dim
    q = {"wq": Param((d, heads * qk), ("layer", "kernel_in", "heads"))}
    if cfg.q_lora_rank:
        q = {"wq_a": Param((d, cfg.q_lora_rank), ("layer", "kernel_in", None)),
             "q_a_norm": Param((cfg.q_lora_rank,), ("layer", None), ones),
             "wq_b": Param((cfg.q_lora_rank, heads * qk),
                           ("layer", None, "heads"))}
    return {
        **norm_shapes(cfg, "attn"),
        **q,
        "wkv_a": Param((d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                       ("layer", "kernel_in", None)),
        "kv_a_norm": Param((cfg.kv_lora_rank,), ("layer", None), ones),
        "wkv_b": Param((cfg.kv_lora_rank,
                        heads * (cfg.qk_nope_dim + cfg.v_head_dim)),
                       ("layer", None, "heads")),
        "wo": Param((heads * cfg.v_head_dim, d),
                    ("layer", "heads", "kernel_in"),
                    residual_out(cfg)),
    }


def _sm_scale(cfg) -> float:
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    if not cfg.kv_lora_rank:
        return cfg.head_dim ** -0.5
    # latent attention: over the whole q/k head, times the square of
    # YaRN's temperature where the model states ``mscale_all_dim``
    scaling = dict(cfg.rope_rule(False)[1])
    return cfg.latent_qk_dim ** -0.5 * yarn_mscale(
        scaling.get("factor", 1.0), scaling.get("mscale_all_dim", 0.0)) ** 2


def _rope_tables(ctx: Ctx, windowed: bool, s: int, dim: int):
    """``(cos, sin) (s, dim / 2)`` a layer of this kind rotates its q and k
    by — the configuration's rule (``cfg.rope_rule``: the theta and the
    scaling group of the model, or of the layer's KIND) through
    ``scaled_rope``: plain or YaRN's frequencies, cos and sin times YaRN's
    ``attention_factor`` — from the rank's offset inside a region that is
    manual over 'sp'.  The ONE place a mixer gets its tables, the softmax
    and the latent one alike (each under the scope ``rope``, inside
    ``attn_qkv``, with the rotations)."""
    offset = jax.lax.axis_index(AXIS_SP) * s if ctx.sp_manual else 0
    if ctx.cfg.block_diffusion:
        # two streams of one sequence: a noised token and its clean copy
        # share a position, so each half is rotated by arange(s / 2)
        return tuple(jnp.concatenate([t, t]) for t in scaled_rope(
            s // 2, dim, *ctx.cfg.rope_rule(windowed)))
    return scaled_rope(s, dim, *ctx.cfg.rope_rule(windowed), offset=offset)


def _rotates_flat(ctx: Ctx, s: int) -> bool:
    """Whether a softmax mixer rotates q and k where the projections leave
    them, ``(b, s, heads x d)`` — the layout the flash kernels read, so no
    copy of q, k or o stands between the projections, RoPE, the kernels
    and the checkpoint's stack —, by the kernel ``ops/rotary.py``, or on
    the 4-D view by ``apply_rope``.  From what the call can see: the
    kernel takes a head of whole lane blocks, as the flash kernels read q
    and k in place (``rotary.fits``); a per-head norm already holds q and k
    to ``(b, s, heads, d)``; 'tp' shards the lanes by heads and a region
    manual over 'sp' leaves the other axes to the partitioner, which
    takes no Mosaic kernel."""
    cfg, mesh = ctx.cfg, ctx.mesh
    return (rotary.fits(s, cfg.head_dim) and not cfg.qk_head_norm
            and not ctx.sp_manual
            and (mesh is None or mesh.shape[AXIS_TP] == 1))


def _rotated(ctx: Ctx, windowed: bool, q, k):
    """q and k rotated by the tables of a layer of this kind (scope
    ``rope``), in the view they come in — the same sums either way: ``(b,
    s, heads, d)`` by ``apply_rope``; ``(b, s, heads x d)`` by the kernel
    (under a mesh per shard of the batch), which hands q on times the
    flash kernels' pre-scale where they are the attention
    (``_q_prescale``).  A kind that rotates the first ``r`` of a head's
    ``d`` dimensions comes in the 4-D view: tables over ``r``, the other
    ``d - r`` columns handed on as they are (scope ``rope_partial``)."""
    cfg = ctx.cfg
    d, r = cfg.head_dim, cfg.rotary_dim(windowed)
    with jax.named_scope("rope"):
        cos, sin = _rope_tables(ctx, windowed, q.shape[1], r)
        if r < d:
            with jax.named_scope("rope_partial"):
                return tuple(jnp.concatenate(
                    [apply_rope(x[..., :r], cos, sin), x[..., r:]], -1)
                    for x in (q, k))
        if q.ndim == 4:
            return apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        c, s = rotary.lane_tables(cos, sin)

        def rotate(q, k, c, s):
            return (rotary.rope_rotate(q, c, s, d, _q_prescale(cfg)),
                    rotary.rope_rotate(k, c, s, d))

        if ctx.mesh is not None:
            rotate = batch_shard_map(rotate, ctx.mesh, (3, 3, None, None),
                                     (3, 3))
        return rotate(q, k, c, s)


def _q_prescale(cfg):
    """What the rotation's kernel multiplies q by on its way out: the flash
    kernels' pre-scale where they are the attention (they then take q as it
    is, ``q_prescaled``), else nothing."""
    if cfg.attn_impl != "flash":
        return None
    return attention.q_prescale(_sm_scale(cfg), cfg.dtype)


def _attention(q, k, v, cfg, mesh, window=None, q_prescaled=False,
               block=None):
    """Dispatch to the configured attention impl; ring / ulysses manage the
    'sp' axis themselves.  ``window``: the keys a query sees, where fewer
    than all before it (flash and the reference alone take one).
    ``q_prescaled``: q comes times the flash kernels' pre-scale
    (``_q_prescale``: flash alone).  ``block``: the rows are two streams
    under the block rule (flash and the reference alone)."""
    impl, scale = cfg.attn_impl, _sm_scale(cfg)
    if mesh is None:
        # Ring/ulysses degenerate to plain attention on one device.
        if impl == "flash":
            return flash_attention(q, k, v, causal=True, sm_scale=scale,
                                   window=window, q_prescaled=q_prescaled,
                                   block=block)
        k, v = repeat_kv_heads(q, k, v)
        return mha_reference(q, k, v, causal=True, sm_scale=scale,
                             window=window, block=block)
    if window is not None and impl in ("ring", "ulysses"):
        raise NotImplementedError(
            "a window over a sequence split over 'sp' (ring, ulysses): each "
            "rank would skip the ranks wholly before its window")
    if impl == "ring":
        return ring_attention(q, k, v, causal=True, sm_scale=scale, mesh=mesh)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, causal=True, sm_scale=scale,
                                 mesh=mesh)
    if impl == "reference":
        k, v = repeat_kv_heads(q, k, v)
        return mha_reference(q, k, v, causal=True, sm_scale=scale,
                             window=window, block=block)
    # flash under a mesh: pallas has no SPMD partitioning rule, so run the
    # kernel per-shard: batch over (dp,fsdp,ep), heads over tp, seq replicated.
    # Manual over EVERY mesh axis — the TPU lowering refuses a Mosaic
    # kernel in a region that leaves any axis to the partitioner.
    # k and v go in with their own heads (the kernels find a q head's KV
    # head themselves) wherever 'tp' divides them: a rank's q heads are
    # then whole groups.  Where it does not, they are repeated first.
    if k.shape[2] % mesh.shape[AXIS_TP]:
        k, v = repeat_kv_heads(q, k, v)
    spec = P(BATCH_AXES, None, AXIS_TP, None)
    fn = manual_shard_map(
        lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, sm_scale=scale, window=window,
            q_prescaled=q_prescaled, block=block),
        set(mesh.axis_names), in_specs=(spec, spec, spec),
        out_specs=spec, mesh=mesh)
    return fn(q, k, v)


def _attention_sp_manual(q, k, v, cfg):
    """Attention inside an already-manual 'sp' region (pipeline path):
    call the sharded bodies inline — no nested shard_map."""
    from ray_tpu.ops.ring_attention import _ring_attention_sharded
    from ray_tpu.ops.ulysses import _ulysses_sharded
    k, v = repeat_kv_heads(q, k, v)
    if cfg.attn_impl == "ulysses":
        return _ulysses_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP,
                                use_flash=False)
    return _ring_attention_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP)


def _window_stats(cfg, sq: int, sk: int, d: int, window):
    """``WINDOW_STATS`` of one call, from shapes alone: the pairs the flash
    schedule's live sub-tiles compute (``causal_tile_counts``) — the whole
    rectangle where no flash kernel runs — over the pairs the window
    leaves, and the live sub-tiles on an edge over all the live ones (the
    one rectangle likewise)."""
    tiles = attention.choose_tiles(
        sq, sk, True, d, cfg.dtype, window=window
    ) if cfg.attn_impl == "flash" else None
    n = attention.causal_tile_counts(sq, sk, *(tiles or (sq, sk, sq, sk)),
                                     window=window)
    executed = n["executed_pairs"] if tiles else sq * sk
    return {WINDOW_EXECUTED: jnp.float32(executed / n["causal_pairs"]),
            WINDOW_MASKED: jnp.float32(
                n["diagonal"] / (n["interior"] + n["diagonal"]))}


def _attend(ctx: Ctx, x, aux, q, k, v, lp, residual: bool, gate=None,
            windowed: bool = False, q_prescaled: bool = False):
    """What every softmax mixer ends in: the attention itself (scope
    ``attention``; ``windowed``: under the model's ``sliding_window``;
    ``q_prescaled``: see ``_attention``), then the heads' outputs side by
    side — times ``sigmoid(gate)`` where the mixer has an output gate —
    through ``wo`` and onto the stream (scope ``attn_out``)."""
    cfg = ctx.cfg
    with jax.named_scope("attention"):
        if windowed:
            if ctx.sp_manual:
                raise NotImplementedError(
                    "a window inside a region that is manual over 'sp'")
            window = attention.live_window(cfg.sliding_window, k.shape[1])
            o = _attention(q, k, v, cfg, ctx.mesh, window, q_prescaled)
            aux = fold(aux, _window_stats(
                cfg, q.shape[1], k.shape[1], max(q.shape[-1], v.shape[-1]),
                window), WINDOW_STATS)
            if cfg.heads_per_layer:
                aux = fold(aux, {WINDOW_KEYS: jnp.float32(
                    window or k.shape[1])}, {WINDOW_KEYS: "max"})
        elif ctx.sp_manual:
            o = _attention_sp_manual(q, k, v, cfg)
        else:
            o = _attention(q, k, v, cfg, ctx.mesh, None, q_prescaled)
    return _out(ctx, x, o, lp, residual, gate), aux


def _out(ctx: Ctx, x, o, lp, residual: bool, gate):
    """Scope ``attn_out``: the heads' outputs ``o (b, s, h, dv)`` side by
    side, gated where the mixer has a gate — ``gate (b, s, h x dv)``, or
    ``(b, s, h)``, one number a head (scope ``attn_head_gate``) —, through
    ``wo`` onto the stream."""
    cfg = ctx.cfg
    with jax.named_scope("attn_out"):
        if gate is not None and cfg.attn_output_gate == "per_head":
            with jax.named_scope("attn_head_gate"):
                o, gate = _head_gated(o, gate, cfg.dtype), None
        o = o.reshape(*x.shape[:2], -1)
        if gate is not None:
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(cfg.dtype)
        return add(ctx, x, o @ lp["wo"].astype(cfg.dtype), residual,
                   out_norm(lp, "attn", cfg))


def _head_gated(o, gate, dtype):
    """``o (b, s, h, dv)`` times ``sigmoid(gate (b, s, h))``, one number a
    head, side by side as ``(b, s, h x dv)`` — where the flash kernels
    leave o and ``wo`` reads it.  The head's number is laid over its ``dv``
    lanes by a 0/1 product on the MXU (exact: one 1 a column): left to a
    broadcast on the 4-D view, XLA writes a float32 array the size of two
    o's, re-lays it to the flat one and copies o beside it, 9-12 ms a layer
    and pass at 64 heads x 16384 tokens (PERF.md §6, PR 83); the product
    itself is float32, rounded once."""
    b, s, h, dv = o.shape
    spread = jnp.repeat(jnp.eye(h, dtype=dtype), dv, axis=1)    # (h, h dv)
    wide = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dtype) @ spread
    return (o.reshape(b, s, h * dv).astype(jnp.float32)
            * wide.astype(jnp.float32)).astype(dtype)


def _qkv(ctx: Ctx, x, lp, windowed: bool):
    """Scope ``attn_qkv`` of a softmax mixer: ``(q, k, v, the output gate
    or None, whether q comes times the flash kernels' pre-scale, the normed
    input)``."""
    cfg, cst = ctx.cfg, ctx.cst
    b, s = x.shape[0], x.shape[1]
    with jax.named_scope("attn_qkv"):
        h = block_in(x, lp["attn_norm"], cfg)
        q = h @ lp["wq"].astype(cfg.dtype)
        k = h @ lp["wk"].astype(cfg.dtype)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        # the kernel swaps the halves of a WHOLE head: a kind that rotates
        # a share of one goes by the 4-D view
        rotate, flat = cfg.rotary(windowed), (
            cfg.rotary_dim(windowed) == cfg.head_dim
            and _rotates_flat(ctx, s))
        if rotate and flat:
            q, k = _rotated(ctx, windowed, q, k)
        q = q.reshape(b, s, cfg.q_heads(windowed), cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_head_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        v = (h @ lp["wv"].astype(cfg.dtype)).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim)
        gate = (h @ lp["wg"].astype(cfg.dtype) if cfg.attn_output_gate
                else None)
        if rotate and not flat:
            q, k = _rotated(ctx, windowed, q, k)
        q = cst(q, ("batch", "seq", "heads", "head_dim"))
        k = cst(k, ("batch", "seq", "kv_heads", "head_dim"))
    return (q, k, v, gate,
            rotate and flat and _q_prescale(cfg) is not None, h)


def _attention_mixer(ctx: Ctx, x, aux, lp, residual: bool = True, *,
                     windowed: bool = False):
    cfg = ctx.cfg
    q, k, v, gate, prescaled, _ = _qkv(ctx, x, lp, windowed)
    if cfg.heads_per_layer:
        # the heads of the q this layer made; the width ``_rotated`` read
        # (the window's keys join where the attention is handed them)
        seen = {Q_HEADS[windowed]: jnp.float32(q.shape[2])}
        if not windowed:
            seen[ROTARY_WIDTH_FULL] = jnp.float32(
                cfg.rotary_dim(False) if cfg.rotary(False) else 0)
        aux = fold(aux, seen, dict.fromkeys(seen, "max"))
    return _attend(ctx, x, aux, q, k, v, lp, residual, gate, windowed,
                   q_prescaled=prescaled)


def _block_stats(cfg, length: int, d: int):
    """``BLOCK_STATS`` of one call over two streams of ``length``
    positions, from shapes alone (``attention.block_tile_counts``,
    ``attention.block_schedule_off``)."""
    block = cfg.bd_block
    tiles = attention.block_tiles(
        length, block, d, cfg.dtype) if cfg.attn_impl == "flash" else None
    needed = attention.block_needed_pairs(length, block)
    if tiles is None:
        return {BLOCK_EXECUTED: jnp.float32(4 * length * length / needed),
                BLOCK_MASK_OFF: jnp.float32(0.0)}
    executed = attention.block_tile_counts(length, tiles)["executed_pairs"]
    return {BLOCK_EXECUTED: jnp.float32(executed / needed),
            BLOCK_MASK_OFF: attention.block_schedule_off(
                length, block, tiles).astype(jnp.float32)}


def _block_mixer(ctx: Ctx, x, aux, lp, residual: bool = True):
    """The softmax mixer of a block-diffusion model: ``x (b, 2 L, d)`` is
    ``[noised ; clean]`` of one sequence, q and k rotated by ``arange(L)``
    in each half (``_rope_tables``), attention under the block rule
    (``ops.attention.block_mask``: ``flash_*_bd`` or the reference)."""
    cfg = ctx.cfg
    if ctx.sp_manual:
        raise NotImplementedError(
            "the block rule inside a region that is manual over 'sp'")
    q, k, v, gate, prescaled, _ = _qkv(ctx, x, lp, False)
    with jax.named_scope("attention"):
        o = _attention(q, k, v, cfg, ctx.mesh, None, prescaled,
                       block=cfg.bd_block)
        aux = fold(aux, _block_stats(
            cfg, q.shape[1] // 2, max(q.shape[-1], v.shape[-1])), BLOCK_STATS)
    return _out(ctx, x, o, lp, residual, gate), aux


def _indexed_shapes(cfg):
    """The softmax mixer's tensors and the indexer's: its queries' and its
    one key's projections, the heads' weights, a LayerNorm over the key."""
    d, heads, di = cfg.embed_dim, cfg.index_heads, cfg.index_dim
    return {
        **_attention_shapes(cfg),
        "wq_idx": Param((d, heads * di), ("layer", "kernel_in", None)),
        "wk_idx": Param((d, di), ("layer", "kernel_in", None)),
        "w_idx": Param((d, heads), ("layer", "kernel_in", None)),
        "k_idx_norm": Param((di,), ("layer", None), ones),
        "k_idx_bias": Param((di,), ("layer", None), constant(0.0)),
    }


def _indexer(ctx: Ctx, h, lp):
    """The indexer's operands from the DETACHED normed input ``h (b, s,
    d)``: its heads' queries ``(b, s, H, di)`` and the one key ``(b, s,
    di)`` (a LayerNorm over it), both rotated by the layer's rule over
    their own ``di`` channels, and the heads' weights ``(b, s, H)`` float32
    times ``H ** -0.5 di ** -0.5``."""
    cfg = ctx.cfg
    b, s = h.shape[:2]
    heads, di = cfg.index_heads, cfg.index_dim
    h = jax.lax.stop_gradient(h)
    q = (h @ lp["wq_idx"].astype(cfg.dtype)).reshape(b, s, heads, di)
    k = _layer_norm(h @ lp["wk_idx"].astype(cfg.dtype), lp["k_idx_norm"],
                    lp["k_idx_bias"], cfg.norm_eps)[:, :, None, :]
    if cfg.rotary(False):
        with jax.named_scope("rope"):
            cos, sin = _rope_tables(ctx, False, s, di)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    w = (h @ lp["w_idx"].astype(cfg.dtype)).astype(jnp.float32) * (
        heads ** -0.5 * di ** -0.5)
    return q, k[:, :, 0, :], w


# The indexer's loss carries its gradient out of the forward pass.  The KL
# reaches nothing but the index scores, and through them q_idx, k_idx and w:
# with the loss ONE number a sequence its cotangent is a scalar a sequence,
# and ``d(operand) = cotangent x (the operand's gradient at cotangent one)``
# exactly — for k_idx too, whose gradient sums over the queries.  So the
# forward rule makes the unit gradients where scores, mask and log-sum-exp
# stand anyway (``sparse_loss``, then ``sparse_scores_bwd`` on its ``(s, s)``
# gradient, each under its scope), the layer checkpoint keeps the three
# (``sparse_attention.UNIT_GRADIENTS``), and the backward rule is three
# multiplies: neither kernel runs again under the checkpoint, no ``(s, s)``
# array is read after the forward pass for the loss's sake, and a program
# that takes no gradient holds ``sparse_loss`` alone.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _indexer_loss(scale, flash, prescaled, q_idx, k_idx, w, scores, sel, q,
                  k, lse2, lse_i):
    """The indexer's KL, a sequence's rows summed ``(b,)``, as a function of
    the indexer's operands alone: everything else comes detached (its
    cotangent is none, and ``index_scores``' own backward is never
    reached)."""
    with jax.named_scope("dsa_loss"):
        kl, _ = sparse_attention.kl_and_gradient(
            scores, sel, q, k, lse2, lse_i, sm_scale=scale, flash=flash,
            q_prescaled=prescaled)
        return jnp.sum(kl, axis=1)


def _indexer_loss_fwd(scale, flash, prescaled, q_idx, k_idx, w, scores, sel,
                      q, k, lse2, lse_i):
    with jax.named_scope("dsa_loss"):
        kl, g = sparse_attention.kl_and_gradient(
            scores, sel, q, k, lse2, lse_i, sm_scale=scale, flash=flash,
            q_prescaled=prescaled)
    with jax.named_scope("dsa_index"):
        unit = sparse_attention.index_grads(q_idx, k_idx, w, g, kernels=flash)
    # kept as ``(b, s, H x d)``: a stack of float32 ``(..., H, 64)`` is laid
    # 128 lanes wide, twice its bytes
    unit = tuple(checkpoint_name(x.reshape(*x.shape[:2], -1), name)
                 for x, name in zip(unit, sparse_attention.UNIT_GRADIENTS))
    # the operands for their shapes and types: the backward reads no value
    return jnp.sum(kl, axis=1), (unit, (q_idx, k_idx, w))


def _indexer_loss_bwd(scale, flash, prescaled, res, ct):
    unit, operands = res
    with jax.named_scope("dsa_loss"):
        # float32 times float32, ONE rounding to the operand's type
        return (*((ct[:, None, None] * u).astype(x.dtype).reshape(x.shape)
                  for u, x in zip(unit, operands)), *(None,) * 6)


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def _selected_attention(cfg, prescaled: bool, q, k, v, q_idx, k_idx, w):
    """What an indexed layer runs on one shard of the batch, each part
    under its scope: the index scores (``dsa_index``), the selection and
    all the rest of the layer takes from the scores — the mask both ways
    round, its log-sum-exp and counts a row — (``dsa_select``), the softmax
    over it (``attention``), the indexer's KL (``dsa_loss``; under a
    gradient ``_indexer_loss``'s forward rule, which also runs
    ``index_scores``' backward kernel under ``dsa_index``, in the FORWARD
    pass).  Returns ``(o (b, s, h, d), kl (b,) the rows' sum, live pairs
    (b,), the share of the selection's row blocks that walked a tie
    (b,))``."""
    scale, flash = _sm_scale(cfg), cfg.attn_impl == "flash"
    with jax.named_scope("dsa_index"):
        scores = sparse_attention.index_scores(q_idx, k_idx, w, kernels=flash)
    with jax.named_scope("dsa_select"):
        tau, tie, walked = sparse_attention.select(scores, cfg.index_topk,
                                                   kernels=flash)
        sel, sel_t, lse_i, live = sparse_attention.masks(
            scores, tau, tie, kernels=flash)
    with jax.named_scope("attention"):
        o, lse2 = sparse_attention.attend(
            q, k, v, sel, sel_t, sm_scale=scale, flash=flash,
            q_prescaled=prescaled)
    kl = _indexer_loss(scale, flash, prescaled, q_idx, k_idx, w,
                       *jax.lax.stop_gradient((scores, sel, q, k, lse2, lse_i)))
    return o, kl, live, walked


def _indexed_mixer(ctx: Ctx, x, aux, lp, residual: bool = True):
    """Softmax attention over the keys a learned indexer picks
    (``ops/sparse_attention.py``): q, k, v as the softmax mixer makes them;
    the indexer's operands from the same normed input, detached; then, per
    shard of the batch under a mesh, scores, selection, attention and the
    indexer's loss.  The loss and the selection's counters ride out of the
    scan as ``INDEX_STATS``."""
    cfg = ctx.cfg
    if ctx.sp_manual:
        raise NotImplementedError(
            "a learned selection inside a region that is manual over 'sp'")
    b, s = x.shape[:2]
    q, k, v, gate, prescaled, h = _qkv(ctx, x, lp, False)
    with jax.named_scope("dsa_index"):
        q_idx, k_idx, w = _indexer(ctx, h, lp)
    run = functools.partial(_selected_attention, cfg, prescaled)
    if ctx.mesh is not None:
        run = batch_shard_map(run, ctx.mesh, (4, 4, 4, 4, 3, 3),
                              (4, 1, 1, 1))
    o, kl, live, walked = run(q, k, v, q_idx, k_idx, w)
    with jax.named_scope("dsa_loss"):
        causal = b * (s * (s + 1) // 2)
        topk = min(cfg.index_topk, s)
        wanted = b * (topk * (topk + 1) // 2 + (s - topk) * topk)
        live = jnp.sum(live)
        aux = fold(aux, {
            INDEX_LOSS: jnp.sum(kl) / (b * s),
            SELECTED_SHARE: live.astype(jnp.float32) / causal,
            SELECTED_OFF: jnp.abs(live - wanted).astype(jnp.float32),
            TIE_WALK_SHARE: jnp.mean(walked),
        }, INDEX_STATS)
    return _out(ctx, x, o, lp, residual, gate), aux


def _latent_mixer(ctx: Ctx, x, aux, lp, residual: bool = True):
    """Latent attention on the residual stream: ``attn_qkv`` holds both
    down-projections, their norms, both up-projections and RoPE (q is ONE
    projection in a model without a q rank).  A head's q and k are
    [no-position part | rotary part] — the k's rotary part is ONE head,
    shared by all and laid beside each head's own part in the one k the
    kernel reads — and its v is narrower; the softmax scale is over the
    whole q/k head.  Whether the rotary part is rotated is the
    configuration's to say (``cfg.rotary``: under ``nope`` it is 64 more
    columns without a position)."""
    cfg, cst = ctx.cfg, ctx.cst
    b, s = x.shape[0], x.shape[1]
    heads, nope, rot = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("attn_qkv"):
        h = block_in(x, lp["attn_norm"], cfg)
        if cfg.q_lora_rank:
            q = (rms_norm(h @ lp["wq_a"].astype(cfg.dtype), lp["q_a_norm"],
                          cfg.norm_eps) @ lp["wq_b"].astype(cfg.dtype)
                 ).reshape(b, s, heads, nope + rot)
        else:
            q = (h @ lp["wq"].astype(cfg.dtype)).reshape(
                b, s, heads, nope + rot)
        c_kv, k_rot = jnp.split(h @ lp["wkv_a"].astype(cfg.dtype),
                                [cfg.kv_lora_rank], -1)
        kv = (rms_norm(c_kv, lp["kv_a_norm"], cfg.norm_eps)
              @ lp["wkv_b"].astype(cfg.dtype)).reshape(
                  b, s, heads, nope + cfg.v_head_dim)
        if cfg.rotary(False):
            with jax.named_scope("rope"):
                cos, sin = _rope_tables(ctx, False, s, rot)
                q = jnp.concatenate(
                    [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], -1)
                k_rot = apply_rope(k_rot[:, :, None, :], cos, sin)
        else:
            k_rot = k_rot[:, :, None, :]
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot, (b, s, heads, rot))],
            -1)
        v = kv[..., nope:]
        q = cst(q, ("batch", "seq", "heads", "head_dim"))
        k = cst(k, ("batch", "seq", "heads", "head_dim"))
    return _attend(ctx, x, aux, q, k, v, lp, residual)


SOFTMAX = Block(_attention_shapes, _attention_mixer,
                saved=attention.SAVED_RESIDUALS, scopes=SCOPES,
                stats=_kind_stats(False))
SLIDING = Block(functools.partial(_attention_shapes, windowed=True),
                functools.partial(_attention_mixer, windowed=True),
                saved=attention.SAVED_RESIDUALS, scopes=SCOPES,
                stats=_kind_stats(True))
LATENT = Block(_latent_shapes, _latent_mixer,
               saved=attention.SAVED_RESIDUALS, scopes=SCOPES)
BLOCK_RULE = Block(_attention_shapes, _block_mixer,
                   saved=attention.SAVED_RESIDUALS, scopes=SCOPES,
                   stats=lambda cfg: BLOCK_STATS)
INDEXED = Block(_indexed_shapes, _indexed_mixer,
                saved=(*attention.SAVED_RESIDUALS,
                       *sparse_attention.SAVED_RESIDUALS),
                scopes=INDEX_SCOPES, stats=lambda cfg: INDEX_STATS)


# ---- differential attention -------------------------------------------------
# Heads in interleaved pairs: q pair p is heads (2p, 2p + 1), KV pair j heads
# (2j, 2j + 1) of k and the two heads of v side by side, one value head of
# 2 x head_dim; KV pair j serves the q pairs of its group as a KV head serves
# its q heads.  With ``a1``, ``a2`` the softmax attention of a pair's first
# and second q head over the pair's first and second k head and the ONE
# doubled value head:
#
#     o_p = RMSNorm(a1 - lambda a2; diff_norm) (1 - lambda_init)
#     lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
#     lambda_init = 0.8 - 0.6 exp(-0.3 i), i the layer's index
#
# The projections are made with their columns in the order [every pair's
# first head | every pair's second]: a permutation of the MATRICES' columns
# (``_halves_first``), so q, k come out as the flash kernels want them — ONE
# grouped-query call of ``heads`` on ``kv_heads`` heads at head_dim / 2 x
# head_dim, the values given once per half, whose output's two halves are a1
# and a2 — and no activation is turned.  The softmaxes, lambda and the
# norm's statistics are float32.

DIFF_KEYS, DIFF_VALUES = "diff_keys", "diff_values"
DIFF_LAMBDA = "diff_lambda"
DIFF_SCOPES = ("attn_qkv", "attention", "attn_diff", "attn_out")
LAMBDA_STD = 0.1


def _diff_shapes(cfg, cross: bool = False):
    """q and o as the softmax mixer's; k and v but for a ``cross`` layer;
    the projections' biases where the model has them (``attn_bias``); four
    vectors of a head's size that make lambda (float32 whatever the
    parameters': lambda moves by hundredths); the weight of the norm over a
    pair's doubled head."""
    d, h, kvd, dh = cfg.embed_dim, cfg.qkv_dim, cfg.kv_dim, cfg.head_dim
    widths = {"q": h} if cross else {"q": h, "k": kvd, "v": kvd}
    axes = {"q": "heads", "k": "kv_heads", "v": "kv_heads"}
    shapes = {**norm_shapes(cfg, "attn")}
    for name, width in widths.items():
        shapes["w" + name] = Param((d, width),
                                   ("layer", "kernel_in", axes[name]))
    shapes["wo"] = Param((h, d), ("layer", "heads", "kernel_in"),
                         residual_out(cfg))
    if cfg.attn_bias:
        for name, width in widths.items():
            shapes["b" + name] = Param((width,), ("layer", axes[name]),
                                       small(BIAS_STD))
        shapes["bo"] = Param((d,), ("layer", "embed"), small(BIAS_STD))
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        shapes[name] = Param((dh,), ("layer", None), small(LAMBDA_STD),
                             jnp.float32)
    shapes["diff_norm"] = Param((2 * dh,), ("layer", None), ones)
    return shapes


def _halves_first(w, head_dim: int):
    """``w (..., heads x head_dim)`` with its columns in the order [the
    pairs' first heads | the pairs' second heads]."""
    lead = w.shape[:-1]
    return jnp.swapaxes(w.reshape(*lead, -1, 2, head_dim), -2, -3).reshape(
        *lead, -1)


def _diff_projection(h, lp, name: str, cfg, halves: bool = True):
    w = lp["w" + name].astype(cfg.dtype)
    turn = (lambda t: _halves_first(t, cfg.head_dim)) if halves else (
        lambda t: t)
    y = h @ turn(w)
    if cfg.attn_bias:
        y = y + turn(lp["b" + name].astype(cfg.dtype))
    return y


def lambda_init(layer_index):
    """arXiv:2410.05258 §3.1, by the layer's index counted from 0."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer_index)


def learned_lambda(lp, start):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, float32."""
    return (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
            - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + start)


def _diff_mixer(ctx: Ctx, x, aux, lp, residual: bool = True, *,
                windowed: bool = False, publishes: bool = False,
                shared=None):
    """-> ``(the stream, aux)``, and from the layer that ``publishes`` a
    third, ``{diff_keys, diff_values}`` (the decoder drops it where no
    later layer reads); with ``shared`` the layer reads those and projects
    q alone."""
    cfg, cst = ctx.cfg, ctx.cst
    b, s = x.shape[:2]
    dh, f32 = cfg.head_dim, jnp.float32
    with jax.named_scope("attn_qkv"):
        h = block_in(x, lp["attn_norm"], cfg, lp.get("attn_norm_bias"))
        q = cst(_diff_projection(h, lp, "q", cfg).reshape(
            b, s, cfg.num_heads, dh), ("batch", "seq", "heads", "head_dim"))
        if shared is None:
            k = cst(_diff_projection(h, lp, "k", cfg).reshape(
                b, s, cfg.num_kv_heads, dh),
                ("batch", "seq", "kv_heads", "head_dim"))
            v = _diff_projection(h, lp, "v", cfg, halves=False).reshape(
                b, s, cfg.num_kv_heads // 2, 2 * dh)
        else:
            k, v = shared[DIFF_KEYS], shared[DIFF_VALUES]
    window, aux = _diff_window(ctx, aux, q, k, windowed)
    with jax.named_scope("attention"):
        both = _attention(q, k, jnp.concatenate([v, v], axis=2), cfg,
                          ctx.mesh, window)
    with jax.named_scope("attn_diff"):
        first, second = jnp.split(both.astype(f32), 2, axis=2)
        start = lambda_init(lp["layer_index"])
        lam = learned_lambda(lp, start)
        o = rms_norm(first - lam * second, lp["diff_norm"], cfg.norm_eps)
        o = (o * (1.0 - start)).astype(cfg.dtype).reshape(b, s, -1)
        aux = fold(aux, {DIFF_LAMBDA: lam}, {DIFF_LAMBDA: "mean"})
    with jax.named_scope("attn_out"):
        y = o @ lp["wo"].astype(cfg.dtype)
        if cfg.attn_bias:
            y = y + lp["bo"].astype(cfg.dtype)
        out = add(ctx, x, y, residual)
    if publishes:
        return out, aux, {DIFF_KEYS: k, DIFF_VALUES: v}
    return out, aux


def _cross_mixer(ctx: Ctx, x, aux, lp, residual: bool = True, *, shared):
    return _diff_mixer(ctx, x, aux, lp, residual, shared=shared)


def _diff_window(ctx: Ctx, aux, q, k, windowed: bool):
    """``(the window that cuts, or None; aux with a windowed layer's
    WINDOW_STATS)``, as ``_attend`` has them."""
    cfg = ctx.cfg
    if ctx.sp_manual:
        raise NotImplementedError(
            "differential attention inside a region that is manual over "
            "'sp'")
    if not windowed:
        return None, aux
    window = attention.live_window(cfg.sliding_window, k.shape[1])
    return window, fold(aux, _window_stats(
        cfg, q.shape[1], k.shape[1], 2 * cfg.head_dim, window), WINDOW_STATS)


def _diff_stats(windowed: bool):
    return lambda cfg: {DIFF_LAMBDA: "mean",
                        **(WINDOW_STATS if windowed else {})}


DIFF_SLIDING = Block(_diff_shapes,
                     functools.partial(_diff_mixer, windowed=True),
                     saved=attention.SAVED_RESIDUALS, scopes=DIFF_SCOPES,
                     stats=_diff_stats(True), indexed=True)
DIFF_FULL = Block(_diff_shapes,
                  functools.partial(_diff_mixer, publishes=True),
                  saved=attention.SAVED_RESIDUALS, scopes=DIFF_SCOPES,
                  stats=_diff_stats(False), indexed=True,
                  publishes=(DIFF_KEYS, DIFF_VALUES))
DIFF_CROSS = Block(functools.partial(_diff_shapes, cross=True), _cross_mixer,
                   saved=attention.SAVED_RESIDUALS, scopes=DIFF_SCOPES,
                   stats=_diff_stats(False), indexed=True,
                   reads=(DIFF_KEYS, DIFF_VALUES))
