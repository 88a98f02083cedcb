"""The tiny models tier-1 builds, one row each: the ``LlamaConfig`` a model's
suite runs at CPU size, the plain reference under ``benchmark/reference``
with its configuration (the public key names), the tokens, how the
parameters are drawn — and ONE cached maker a process of a row's program
(``program``: configuration, parameters, and its loss, gradients and
per-token losses as compiled functions) and of what its reference says of
the same parameters (``reference``).

The tests every model repeats are ONE function each here, and a model's
file calls them in three lines: ``against_the_reference`` (loss, per-token
losses and gradients equal the reference's), ``stands_apart`` (a row's
``faults``: a part got wrong moves the loss by more than the check's
tolerance), ``shares_add_up`` (the row's ``shares`` chips' parts of one
expert layer are the uncut layer) and ``train_step_reports`` (the row's
``step``: the train step opens its scopes, reports its counters and
learns).  A new model is a row here — with its faults, its share count and
its step —, its reference, and a file of tests of what is NEW in it.  Not
collected by pytest (no ``test_`` in its name); ``tests/test_blocks.py``
holds the table to every registered block."""

import contextlib
import dataclasses
import functools
import math
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import (
    afmoe, granite_hybrid, joyai_flash, keye_sparse, kimi_linear, laguna,
    lfm2_moe, mellum, nemotron3, nemotron_h, olmo_hybrid, olmoe, ouro_looped,
    phi4flash, sdar_block_diffusion, solar_open2, xing4)
from ray_tpu.models import llama
from ray_tpu.models.blocks import (
    MIXERS, attention as attention_block, conv, ffn, kda, mamba, mamba1)
from ray_tpu.models.blocks.delta import GDN_STATE_ABSMAX
from ray_tpu.models.blocks.kda import (
    KDA_BETA_MAX, KDA_CHUNK_DECAY_MIN, KDA_STATE_ABSMAX)
from ray_tpu.models.llama import (
    ROPE_BY_KIND, LlamaConfig, forward, init_params, loss_fn)
from ray_tpu.ops import layers, sparse_attention
from ray_tpu.ops.moe import moe_block
from ray_tpu.ops.ssm import causal_conv1d
from ray_tpu.train.core import (
    STEP_SCOPES, default_optimizer, init_train_state, make_train_step)
from ray_tpu.util.tracing import scope_and_phase

S, F = "sliding_attention", "full_attention"


def drawn(params, seed=0, also=()):
    """``params`` with the norm weights (and the tensors named in ``also``)
    drawn away from 1 by numpy's generator ``seed``, as the train loop
    draws them for its check."""
    rng = np.random.default_rng(seed)

    def leaf(path, a):
        name = str(getattr(path[-1], "key", ""))
        if not (name.endswith("norm") or name in also):
            return a
        return a * rng.uniform(0.5, 1.5, a.shape).astype(np.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def seeded(cfg, seed=0, draw=None, also=()):
    """Parameters from ``PRNGKey(seed)``, ``drawn`` by the seed again
    unless ``draw`` names another."""
    return drawn(init_params(jax.random.PRNGKey(seed), cfg),
                 seed if draw is None else draw, also)


def _olmoe_params(cfg):
    """The layers' norms away from their initial ones by JAX's generator,
    so that leaving one out shows."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    params["layers"] = {
        name: (a * jax.random.uniform(next(keys), a.shape, jnp.float32,
                                      0.5, 1.5).astype(a.dtype)
               if name.endswith("norm") else a)
        for name, a in params["layers"].items()}
    return params


def _keye_params(cfg):
    """``seeded``, and the indexer's LayerNorm bias (drawn at 0) away from
    it, so that leaving it out shows."""
    params = seeded(cfg)
    bias = params["layers"]["k_idx_bias"]
    params["layers"]["k_idx_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(11), bias.shape, bias.dtype)
    return params


def _ouro_params(cfg):
    """``seeded``, with the exit gate four times its draw and its bias
    (drawn at 0) away from it: the exits then stand far from uniform, and a
    fault in how the passes are weighted shows (a model without the group
    has no gate: ``seeded``)."""
    params = seeded(cfg)
    if not cfg.looped:
        return params
    return dict(params, exit_gate=4.0 * params["exit_gate"],
                exit_gate_bias=params["exit_gate_bias"] + 0.3)


def _jax_tokens(rows, positions, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(1), (rows, positions), 0,
                              vocab)


def _numpy_tokens(rows, positions):
    return jnp.asarray(np.random.default_rng(7).integers(
        0, 256, (rows, positions), dtype=np.int32))


class Row(NamedTuple):
    fields: Dict[str, Any]          # over ``LlamaConfig.tiny``'s own
    tokens: jax.Array               # (rows, positions + 1)
    reference: Any = None           # the module under benchmark/reference
    conf: Optional[Dict] = None     # its configuration, public key names
    params: Callable = seeded       # cfg -> parameters
    precision: Optional[str] = None  # matmul precision of BOTH sides
    faults: Tuple = ()              # ``Fault``s: the model got wrong
    sees: Tuple = ()                # (distance, over): how a fault shows
    sound: Tuple = ({}, {})         # (fields, the reference's keys) the
    #                                 faults are put into, over the row's
    sound_within: Optional[float] = None   # ... and how far off, a token's
    #                                 loss at most, the sound program stands
    shares: int = 0                 # chips an expert layer is cut over
    step: Any = None                # ``Step``: the train step's test


_SMALL = dict(vocab_size=128, embed_dim=64, num_heads=4, head_dim=16,
              mlp_dim=32, max_seq_len=64, dtype=jnp.float32, remat=False,
              attn_impl="reference")
_LATENT = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
               v_head_dim=16)
_SIGMOID = dict(norm_topk_prob=True, router_scoring="sigmoid",
                topk_method="noaux_tc", aux_loss_coef=0.0)

XING4_SCALING = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                 "mscale_all_dim": 1, "original_max_position_embeddings": 16,
                 "type": "yarn"}
GRANITE_TYPES = ("mamba", "attention", "mamba", "mamba", "attention")
OLMO_HYBRID_TYPES = ("linear_attention",) * 3 + (F, "linear_attention")
LFM2_PATTERN = ("conv", "conv", F, "conv", "conv", "conv", F, "conv")
TRINITY_PATTERN = (S, S, S, F, S)   # the file's: one dense layer, s s f s
TRINITY_WINDOW = 16         # of 48: a third of each later row's keys cut off
MELLUM_PATTERN = (S, S, S, F) * 2   # two periods
MELLUM_WINDOW = 16
# Mellum's tiny rule: the sample's 48 positions pass the original 16, and
# c(1) = 1.62 lies between two pairs, so the bounds' truncation shows
MELLUM_YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}
MELLUM_GROUPS = {F: MELLUM_YARN, S: {"rope_type": "default",
                                     "rope_theta": 100}}
# Laguna's per-layer lists in small, two periods: a FULL layer first, the
# head count by the kind (2 KV heads: groups of 2 and 3), the dense FFN in
# layer 0 alone.  The full layers rotate HALF a head (8 of 16 dimensions) by
# YaRN reckoned over those 8 — c(1) = 0.81, so pair 0 keeps its frequency
# and pairs 1-3 have it divided by 4 —, the sliding ones the whole head
LAGUNA_PATTERN = (F, S, S, S) * 2
LAGUNA_HEADS = (4, 6, 6, 6) * 2
LAGUNA_FFNS = ("dense",) + ("sparse",) * 7
LAGUNA_WINDOW = 16
LAGUNA_YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 64,
               "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1,
               "partial_rotary_factor": 0.5}
LAGUNA_GROUPS = {F: LAGUNA_YARN,
                 S: {"rope_type": "default", "rope_theta": 100,
                     "partial_rotary_factor": 1},
                 "original_max_position_embeddings": 16}
# Kimi-Linear's lists in small, counted from 1 and longer than the model:
# layers 1-5 are run, K K K F K, the first with the dense FFN
KIMI_LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
               "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4}
# Solar-Open-2's keys in small: the SOFTMAX layers counted from 0 and past
# the model's depth (layers 0-3 are run, G K K K), the KDA sizes alone in
# the group, as many value heads as key heads
SOLAR_GQA = (0, 4, 8)
SOLAR_LINEAR = {"num_heads": 4, "head_dim": 16, "num_kv_heads": None,
                "short_conv_kernel_size": 4}
# Keye-VL-2.0's group in small: 4 index heads of 16 against one key, 16 keys
# a query of the sample's 128 (an eighth of the last row's: neither empty
# nor everything)
KEYE_INDEXER = {"indexer_num_heads": 4, "indexer_head_dim": 16,
                "indexer_num_kv_heads": 1, "topk": 16, "q_chunk_size": 512,
                "kv_chunk_size": 512}
# SDAR's objective in small, as hashable pairs (a row's fields and a
# reference's keys are cached by value): blocks of 4 of the sample's 64
# positions, the mask token the vocabulary's last, a seed of its own
SDAR_NOISE = (("block_length", 4), ("eps", 1e-3), ("mask_token_id", 127),
              ("noise_seed", 5))
# Ouro's group in small, as hashable pairs; ``looped(T)`` is the pair of a
# row's field and its reference's keys at another count of passes
OURO_LOOP = (("entropy_coef", 0.05), ("passes", 4))
NEMOTRON_PATTERN = "MEM*EMEME"  # longer than the model: the first 5 are run
NEMOTRON3_MODULE = "*E"         # the predicted-ahead module's own pattern

# The Nemotron-H layers in small, M, E, M, *, E: 2 groups of 4 heads; 16
# experts of width 32, the held ones from 4 on; a shared expert of width 48;
# 8 query heads over 2 KV heads.  A row adds how many are held, the choices
# a token and the gates' scale.
_NEMOTRON = dict(
    vocab_size=128, embed_dim=64, num_layers=5,
    layer_pattern=NEMOTRON_PATTERN, num_heads=8, num_kv_heads=2, head_dim=16,
    position_embedding="nope", norm_eps=1e-5, max_seq_len=64,
    dtype=jnp.float32, remat=False, attn_impl="reference", ssm_heads=8,
    ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_conv=4, ssm_chunk=8,
    ffn_act="relu2", mlp_dim=32, shared_experts=1, shared_mlp_dim=48,
    num_experts=16, first_expert=4, topk_norm_eps=1e-20, **_SIGMOID)
_NEMOTRON_CONF = dict(
    hybrid_override_pattern=NEMOTRON_PATTERN, num_hidden_layers=5,
    layer_norm_epsilon=1e-5, num_attention_heads=8, num_key_value_heads=2,
    mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=8, n_groups=2,
    first_expert=4)

# -- the faults: a part of a model got wrong -----------------------------------

class Frozen(dict):
    """A dict that ``program`` / ``reference`` can take among their
    (hashed) overrides: a public file's nested group, never changed."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def mean_apart(side, params, want):
    """The total loss's distance from the reference's, relative."""
    total = float(want.parts["total"])
    return abs(float(side.loss(params)[0]) - total) / total


def _token_apart(side, params, want):
    return side.token_nll(params) - want.parts["token_nll"]


def token_rms(side, params, want):
    """The per-token losses' distance from the reference's, RMS (nats)."""
    return float(jnp.sqrt(jnp.mean(jnp.square(
        _token_apart(side, params, want)))))


def token_max(side, params, want):
    """The per-token losses' distance from the reference's, at most."""
    return float(jnp.max(jnp.abs(_token_apart(side, params, want))))


def part_apart(part):
    """One named part's distance from the reference's, relative."""
    def distance(side, params, want):
        got = side.loss(params)[1][part]
        return abs(float(got) / float(want.parts[part]) - 1)

    return distance


class Fault(NamedTuple):
    """One way to get a row's model wrong, put into the PROGRAM: overrides
    of the row's fields, ONE function of the program replaced (``patch``,
    handed a ``pytest.MonkeyPatch``: the changed program is then traced
    under it and nothing of it is kept), the sound tensors as the wrong
    program reads them (``reread(params, cfg)``), or — where the fields
    change the tensors' SHAPES — the changed program on its own draw.
    ``distance`` / ``over`` in place of the row's ``sees``; ``under``: a
    fault NO check can see, which the test says rather than claim."""
    id: str
    fields: Dict[str, Any] = {}
    patch: Optional[Callable] = None
    reread: Optional[Callable] = None
    own_draw: bool = False
    distance: Optional[Callable] = None
    over: Optional[float] = None
    under: Optional[float] = None
    group: str = "part"


def _in_stacks(change, where=lambda stack: True):
    """``reread``: ``change(stack)`` in place of every stack of layers that
    ``where`` picks."""
    def reread(params, cfg):
        return dict(params, layers=tuple(
            change(s) if where(s) else s for s in params["layers"]))

    return reread


def _tensors(runs=None, **changes):
    """``reread``: ``changes[name](tensor)`` in place of the named tensors
    of the runs of layers ``runs`` (of every run that holds them all)."""
    def reread(params, cfg):
        return dict(params, layers=tuple(
            dict(s, **{name: fn(s[name]) for name, fn in changes.items()})
            if (all(name in s for name in changes) if runs is None
                else i in runs) else s
            for i, s in enumerate(params["layers"])))

    return reread


def _whole_width_norm(patch):
    norm = mamba.gated_rms_norm
    patch.setattr(mamba, "gated_rms_norm",
                  lambda y, z, w, eps, groups: norm(y, z, w, eps))


_JOYAI_FAULTS = (
    Fault("gate_scale", dict(routed_scaling_factor=1.0)),
    Fault("shared_expert", dict(shared_experts=0)),
    Fault("renormalised", dict(norm_topk_prob=False)),
    Fault("mtp_weight", dict(mtp_loss_coef=0.0)),
    Fault("rope_theta", dict(rope_theta=1e4)),
    Fault("the_other_host", dict(first_expert=8)))




_XING4_FAULTS = (
    Fault("rope_scaling", dict(rope_scaling=None)),     # YaRN and its scale
    Fault("routed_scaling_factor", dict(routed_scaling_factor=1.0)),
    Fault("router_scoring", dict(router_scoring="softmax")),
    Fault("norm_topk_prob", dict(norm_topk_prob=False)),
    Fault("first_expert", dict(first_expert=0)),    # another chip's experts
    Fault("hc_sinkhorn_iters", dict(hc_sinkhorn_iters=0)),      # exp alone
    Fault("hc_clamp_max", dict(hc_clamp_max=0.0)),
    Fault("mtp_loss_coef", dict(mtp_loss_coef=0.0)),
    # the expert stack with one tensor at 0
    *(Fault(leaf, reread=_tensors((1,), **{leaf: jnp.zeros_like}), over=2e-4,
            group="leaf")
      for leaf in ("router_bias", "shared_down", "hc_attn_bias",
                   "hc_ffn_scale", "wkv_b")))


def _decay_a_head(patch):   # the mean over its channels: Olmo-Hybrid's rule
    rule = kda.kda_chunked
    patch.setattr(kda, "kda_chunked", lambda q, k, v, g, b: rule(
        q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
        b))


# THE ROTATION moves the MEAN of 192 positions by 1.3e-4 only, under its
# tolerance (8 of 24 columns in one layer of five; signed differences
# cancel): it is the PER-TOKEN comparison that sees it, 0.127 nats RMS where
# the sound program reads 8e-7
_KIMI_FAULTS = (
    Fault("position_embedding", dict(position_embedding="rope"),
          distance=token_rms, over=0.05),
    Fault("routed_scaling_factor", dict(routed_scaling_factor=1.0)),
    Fault("shared_experts", dict(shared_experts=0)),
    Fault("norm_topk_prob", dict(norm_topk_prob=False)),
    Fault("first_expert", dict(first_expert=8)),
    Fault("leading_dense-dense_mlp_dim",
          dict(leading_dense=0, dense_mlp_dim=0), own_draw=True),
    Fault("silu gate", patch=lambda patch: patch.setattr(
        kda, "_gate", jax.nn.silu)),
    Fault("beta twice", patch=lambda patch: patch.setattr(
        kda, "_beta", lambda b: 2.0 * jax.nn.sigmoid(b))),
    Fault("decay a head", patch=_decay_a_head),
    Fault("latent in a kda layer", dict(linear_attn_config=dict(
        KIMI_LINEAR, kda_layers=[1, 2, 5], full_attn_layers=[3, 4])),
        own_draw=True))

_SOLAR_FAULTS = (
    Fault("kda_neg_eigval=False", dict(kda_neg_eigval=False)),
    Fault("attn_output_gate=False", dict(attn_output_gate=False)),
    Fault("position_embedding=rope", dict(position_embedding="rope"),
          distance=token_rms, over=0.01),
    Fault("routed_scaling_factor=2.0", dict(routed_scaling_factor=2.0)),
    Fault("shared_experts=0", dict(shared_experts=0)),
    Fault("norm_topk_prob=False", dict(norm_topk_prob=False)),
    Fault("first_expert=8", dict(first_expert=8)),
    Fault("num_kv_heads=4", dict(num_kv_heads=4), own_draw=True),
    Fault("gqa_layers=(1, 5)", dict(gqa_layers=(1, 5)), own_draw=True))




def _silu_in_the_conv(patch):
    def with_silu(bcx, w):
        gate_in, gate_out, x = jnp.split(bcx, 3, -1)
        return gate_out * causal_conv1d(gate_in * x, w)

    patch.setattr(conv, "gated_short_conv", with_silu)


_LFM2_FAULTS = (
    Fault("no-head-norm", dict(qk_head_norm=False)),
    Fault("whole-projection-norm", dict(qk_head_norm=False, qk_norm=True),
          reread=_tensors((1, 3), q_norm=lambda a: jnp.tile(a, (1, 4)),
                          k_norm=lambda a: jnp.tile(a, (1, 2)))),
    # the renormalisation's 1e-6 moves a gate by a millionth: below
    # anything a check resolves
    Fault("no-topk-eps", dict(topk_norm_eps=0.0), under=2e-5),
    Fault("softmax-scores", dict(router_scoring="softmax")),
    Fault("no-bias", dict(topk_method="greedy"), reread=_in_stacks(
        lambda s: {k: v for k, v in s.items() if k != "router_bias"})),
    Fault("silu-in-the-conv", patch=_silu_in_the_conv),
    # [B | C | x] read as [x | C | B] is the same function; [C | B | x] not
    Fault("gates-swapped", reread=_tensors(
        (0, 2, 4), sconv_in=lambda a: jnp.concatenate(
            [a[..., 64:128], a[..., :64], a[..., 128:]], -1))),
    Fault("taps-reversed", reread=_tensors(
        (0, 2, 4), sconv_w=lambda a: a[:, ::-1])),
    Fault("untied-head", dict(tie_embeddings=False),
          reread=lambda params, cfg: dict(params, lm_head=init_params(
              jax.random.PRNGKey(5), cfg)["lm_head"])))


def _mellum_groups(**kinds):
    """Mellum's two groups of rotary keys with a kind's changed (a dict)
    or taken from the other kind (its name)."""
    return {k: dict(MELLUM_GROUPS[k], **change) if isinstance(change, dict)
            else MELLUM_GROUPS[change] for k, change in kinds.items()}


def _ramp_not_truncated(patch):
    """The upper bound left a fraction (c(1) = 1.62 where the rule says
    ceil: 2) moves the ramp's one step inside, 0.5 to 0.62."""
    def untruncated(head_dim, theta, *, factor, original, beta_fast=32.0,
                    beta_slow=1.0):
        def c(n):
            return (head_dim * math.log(original / (n * 2 * math.pi))
                    / (2 * math.log(theta)))

        low, high = max(c(beta_fast), 0.0), c(beta_slow)
        plain = 1.0 / theta ** (jnp.arange(0, head_dim, 2) / head_dim)
        ramp = jnp.clip((jnp.arange(head_dim // 2) - low) / (high - low),
                        0.0, 1.0)
        return plain / factor * ramp + plain * (1.0 - ramp)

    patch.setattr(layers, "yarn_inv_freq", untruncated)


_MELLUM_FAULTS = (
    Fault("yarn-dropped", dict(rope_parameters=_mellum_groups(**{F: S, S: S}))),
    Fault("attention-factor-dropped", dict(rope_parameters=_mellum_groups(
        **{F: {"attention_factor": 1.0}, S: S}))),
    Fault("tables-swapped",
          dict(rope_parameters=_mellum_groups(**{F: S, S: F}))),
    Fault("yarn-in-the-windowed-layers-too",
          dict(rope_parameters=_mellum_groups(**{F: F, S: F}))),
    Fault("ramp-not-truncated", patch=_ramp_not_truncated),
    Fault("no-rope-in-the-full-layers",
          dict(position_embedding="rope_windowed", rope_theta=100.0)),
    Fault("no-window", dict(sliding_window=48)),    # the sample's positions
    Fault("window-one-short", dict(sliding_window=MELLUM_WINDOW - 1)),
    Fault("window-four-times", dict(sliding_window=4 * MELLUM_WINDOW)),
    Fault("gates-not-renormalised", dict(norm_topk_prob=False)),
    Fault("another-chips-experts", dict(first_expert=0)),
    # what the chip's mean-loss row sees: 0.001 x E-ish of ln(vocab)
    Fault("no-balance-loss", dict(aux_loss_coef=0.0), distance=mean_apart,
          over=2 * mellum.LOSS_RTOL))

def _laguna_groups(**full):
    """Laguna's rotary groups with the full layers' changed."""
    return dict(LAGUNA_GROUPS, **{F: dict(LAGUNA_YARN, **full)})


def _factor_on_the_unrotated_half(patch):
    rotated = attention_block._rotated

    def wrong(ctx, windowed, q, k):
        q, k = rotated(ctx, windowed, q, k)
        if windowed:
            return q, k
        scale = jnp.where(jnp.arange(q.shape[-1]) < ctx.cfg.rotary_dim(False),
                          1.0, LAGUNA_YARN["attention_factor"])
        return q * scale, k * scale

    patch.setattr(attention_block, "_rotated", wrong)


def _yarn_over_the_whole_head(patch):
    tables = attention_block._rope_tables
    patch.setattr(
        attention_block, "_rope_tables",
        lambda ctx, windowed, s, dim: tuple(
            t[:, :dim // 2] for t in tables(ctx, windowed, s,
                                            ctx.cfg.head_dim)))


def _sliding_layers_at_four_heads(stack):
    """A sliding run's tensors as a program of 4 query heads everywhere
    reads them: the first 4 of its 6 heads."""
    if stack["wg"].shape[-1] != 6:
        return stack
    return dict(stack, wq=stack["wq"][..., :64], wo=stack["wo"][:, :64],
                wg=stack["wg"][..., :4])


_LAGUNA_FAULTS = (
    Fault("full-head-count-in-the-sliding-layers",
          dict(heads_per_layer=(4,) * 8),
          reread=_in_stacks(_sliding_layers_at_four_heads)),
    Fault("whole-head-rotated-in-the-full-layer", dict(
        rope_parameters=_laguna_groups(partial_rotary_factor=1.0))),
    Fault("factor-on-the-unrotated-half",
          patch=_factor_on_the_unrotated_half),
    Fault("yarn-over-the-whole-head", patch=_yarn_over_the_whole_head),
    Fault("attention-factor-dropped", dict(
        rope_parameters=_laguna_groups(attention_factor=1.0))),
    Fault("plain-tables-in-the-full-layer", dict(
        rope_parameters=_laguna_groups(rope_type="default"))),
    Fault("no-gate", dict(attn_output_gate=False)),
    Fault("window-one-short", dict(sliding_window=LAGUNA_WINDOW - 1)),
    Fault("no-window", dict(sliding_window=48)),    # the sample's positions
    Fault("softmax-scores", dict(router_scoring="softmax")),
    Fault("no-bias", dict(topk_method="greedy")),
    Fault("no-route-scale", dict(routed_scaling_factor=1.0)),
    Fault("gates-not-renormalised", dict(norm_topk_prob=False)),
    Fault("no-shared-expert", dict(shared_experts=0)),
    Fault("another-chips-experts", dict(first_expert=0)))

_TRINITY_FAULTS = (
    Fault("rope-in-the-full-layer-too", dict(position_embedding="rope")),
    Fault("no-rope-in-the-windowed-layers", dict(position_embedding="nope")),
    Fault("rope-in-the-full-layer-alone", patch=lambda patch: patch.setattr(
        LlamaConfig, "rotary", lambda self, windowed: not windowed)),
    Fault("no-window", dict(sliding_window=48)),    # the sample's positions
    Fault("window-one-short", dict(sliding_window=TRINITY_WINDOW - 1)),
    Fault("no-output-gate", dict(attn_output_gate=False)),
    Fault("no-post-norms", dict(block_norm="input")),
    Fault("no-head-norm", dict(qk_head_norm=False)),
    Fault("no-embedding-scale", dict(embedding_multiplier=1.0)),
    Fault("no-bias", dict(topk_method="greedy")),
    Fault("no-route-scale", dict(routed_scaling_factor=1.0)),
    Fault("no-shared-expert", dict(shared_experts=0)))


def _one_groups_b_and_c(patch):
    scan = mamba.ssd_chunked
    patch.setattr(mamba, "ssd_chunked", lambda x, dt, a, b, c, d, chunk: scan(
        x, dt, a, jnp.repeat(b[:, :, :1], b.shape[2], 2),
        jnp.repeat(c[:, :, :1], c.shape[2], 2), d, chunk=chunk))


_NEMOTRON_FAULTS = (
    # the same weights read as a SwiGLU model whose gate is its up
    Fault("gate_in_place_of_relu2", dict(ffn_act="swiglu"),
          reread=_in_stacks(lambda s: dict(
              s, w_gate=s["w_up"], shared_gate=s["shared_up"]),
              lambda s: "w_up" in s)),
    Fault("norm_over_the_whole_width", patch=_whole_width_norm),
    Fault("one_groups_b_and_c_for_all_heads", patch=_one_groups_b_and_c),
    Fault("selection_bias_zeroed",
          reread=_tensors(router_bias=jnp.zeros_like)),
    Fault("shared_expert_at_the_experts_width", dict(shared_mlp_dim=0),
          reread=_tensors(shared_up=lambda a: a[..., :32],
                          shared_down=lambda a: a[:, :32])),
    Fault("rope_switched_on", dict(position_embedding="rope")),
    Fault("gate_scale_left_out", dict(routed_scaling_factor=1.0)),
    Fault("the_next_chips_experts", dict(first_expert=12)))


def _in_expert_stacks(change):
    """``reread``: ``change(stack)`` in place of every expert stack, the
    stack's and the predicted-ahead module's."""
    def reread(params, cfg):
        swap = lambda stacks: tuple(  # noqa: E731
            change(s) if "router" in s else s for s in stacks)
        return dict(params, layers=swap(params["layers"]), mtp=dict(
            params["mtp"], layers=swap(params["mtp"]["layers"])))

    return reread


def _shared_expert_fed_the_round_trip(patch):
    dense = ffn._ffn

    def fed(h, lp, cfg, prefix="w_"):
        if prefix == "shared_":
            h = (h @ lp["w_latent_in"]) @ lp["w_latent_out"]
        return dense(h, lp, cfg, prefix)

    patch.setattr(ffn, "_ffn", fed)


# three of them are a published key READ WRONG (``routed_scaling_factor``,
# ``num_experts_per_tok``, ``mtp_hybrid_override_pattern``), two the
# latent's wiring (``moe_latent_size``)
_NEMOTRON3_FAULTS = (
    Fault("gate_scale_left_out", dict(routed_scaling_factor=1.0)),
    Fault("top_21_in_place_of_top_22", dict(num_selected=5)),  # 5 of 6
    # u = h W_out^T, out = y W_in^T
    Fault("the_latent_pair_crossed", reread=_in_expert_stacks(lambda s: dict(
        s, w_latent_in=s["w_latent_out"].swapaxes(1, 2),
        w_latent_out=s["w_latent_in"].swapaxes(1, 2)))),
    Fault("shared_expert_fed_the_latents_round_trip",
          patch=_shared_expert_fed_the_round_trip),
    # the stack is sound: it is the module's loss that parts
    Fault("the_modules_e_before_its_star", dict(mtp_pattern="E*"),
          reread=lambda params, cfg: dict(params, mtp=dict(
              params["mtp"], layers=params["mtp"]["layers"][::-1])),
          distance=part_apart("mtp_loss"), over=2e-3),
    Fault("norm_over_the_whole_width", patch=_whole_width_norm))


_with = dataclasses.replace


def _memory_fault(remade):
    """The Mamba-1 block publishing ``remade(m, what the real block
    publishes with D at 0, the gate's silu(z))`` in place of ``m``."""
    def fault(patch):
        real = mamba1.BLOCK.apply

        def apply(ctx, x, aux, lp, residual=True):
            out, aux_out, made = real(ctx, x, aux, lp, residual)
            _, _, bare = real(ctx, x, aux, dict(
                lp, s6_D=jnp.zeros_like(lp["s6_D"])), residual)
            h = mamba1.block_in(x, lp["s6_norm"], ctx.cfg,
                                lp["s6_norm_bias"])
            z = (h @ lp["s6_in"])[..., ctx.cfg.s6_inner:]
            return out, aux_out, {mamba1.MEMORY: remade(
                made[mamba1.MEMORY], bare[mamba1.MEMORY], jax.nn.silu(z))}

        patch.setitem(MIXERS, "mamba1", _with(mamba1.BLOCK, apply=apply))

    return fault


def _cross_reads_the_windowed_layer(patch):
    patch.setitem(MIXERS, "diff_full", _with(
        attention_block.DIFF_FULL, publishes=()))
    patch.setitem(MIXERS, "diff_sliding", _with(
        attention_block.DIFF_SLIDING,
        publishes=attention_block.DIFF_FULL.publishes,
        apply=functools.partial(attention_block._diff_mixer, windowed=True,
                                publishes=True)))
    assert llama._published(tiny("phi4flash").layer_runs)[3] == (
        "diff_keys", "diff_values")


_PHI4FLASH_FAULTS = (
    Fault("no-lambda", patch=lambda patch: patch.setattr(     # a1 - a2
        attention_block, "learned_lambda", lambda lp, start: 1.0)),
    Fault("no-sub-norm", patch=lambda patch: patch.setattr(
        attention_block, "rms_norm", lambda x, w, eps: x)),
    Fault("one-lambda-init", patch=lambda patch: patch.setattr(
        attention_block, "lambda_init", lambda i: 0.2 + 0.0 * i)),
    Fault("window-one-longer", dict(sliding_window=9)),
    Fault("cross-reads-the-windowed-layer",
          patch=_cross_reads_the_windowed_layer),
    Fault("unit-reads-after-the-gate",
          patch=_memory_fault(lambda m, bare, gate: m * gate)),
    Fault("memory-without-d-x",
          patch=_memory_fault(lambda m, bare, gate: bare)),
    Fault("no-dt-bias", reread=_tensors(s6_dt_bias=jnp.zeros_like)))


def _keye_losses_apart(side, params, want):
    """The indexers' loss by 2e-3 or the next-token loss by 1e-4: the
    larger of the two in units of its bound."""
    _, parts = side.loss(params)
    return max(abs(float(parts[part]) - float(want.parts[part])) / bound
               for part, bound in (("idx_loss", 2e-3), ("loss", 1e-4)))


def _keye_wq_gradient_apart(side, params, want):
    """Values stand; the model's gradients take the indexers' loss in."""
    _, grads = side.value_and_grad(params)
    return apart(grads["layers"]["wq"], want.grads["layers"]["wq"])


def _head_weights_left_out(patch):
    indexer = attention_block._indexer
    patch.setattr(attention_block, "_indexer", lambda *a: (
        lambda q, k, w: (q, k, jnp.ones_like(w) / 32))(*indexer(*a)))


_KEYE_FAULTS = (
    Fault("relu left out", patch=lambda patch: patch.setattr(
        jax.nn, "relu", lambda x: x)),
    Fault("head weights left out", patch=_head_weights_left_out),
    Fault("topk halved", dict(sa_config=Frozen(KEYE_INDEXER, topk=8))),
    Fault("the key's norm left out", patch=lambda patch: patch.setattr(
        attention_block, "_layer_norm", lambda x, w, b, eps: x)),
    Fault("the loss aimed at an un-detached target",
          patch=lambda patch: patch.setattr(
              sparse_attention.jax.lax, "stop_gradient", lambda x: x),
          distance=_keye_wq_gradient_apart, over=1e-3))


# -- the train steps: ``train_step_reports``'s arguments, a row each ----------

class Step(NamedTuple):
    """The train step of ``tiny(name, **fields)`` under ``optimizer()``:
    ``steps`` steps of it, the names its lowered text must hold (scopes as
    ``"scope/"``, kernels by name), the scopes its compiled ops must carry
    in all three phases, the counters among its metrics, and whether the
    loss must fall over the steps."""
    fields: Dict[str, Any] = {}
    named: Tuple[str, ...] = ()
    phased: Tuple[str, ...] = ()
    counters: Tuple[str, ...] = ()
    steps: int = 3
    learns: bool = True
    optimizer: Callable = default_optimizer


_KERNELS = dict(attn_impl="flash", remat=True)   # as a chip runs the model
_ADAM = functools.partial(optax.adam, 1e-2)
_MOE_COUNTERS = ("moe_held_share", "moe_dropped", "moe_rows_visited_share",
                 "moe_load_max_over_mean")
_WINDOWED = ("flash_fwd_win", "flash_dkv_win", "flash_fwd", "flash_dkv/",
             "attn_out/", "moe_experts/", "moe_combine/")
GDN_SCOPES = ("gdn_in", "gdn_conv", "gdn_scan", "gdn_out")
KDA_SCOPES = ("kda_in", "kda_conv", "kda_scan", "kda_out")
SCONV_SCOPES = ("sconv_in", "sconv_gate", "sconv_out")

ROWS: Dict[str, Row] = {
    # ``LlamaConfig.tiny`` bare and with experts: no reference of their own
    "dense": Row({}, _numpy_tokens(2, 33)),
    "moe": Row(dict(num_experts=4, num_selected=2, z_loss_coef=0.001,
                    qk_norm=True), _numpy_tokens(2, 33)),
    "olmoe": Row(
        dict(num_experts=8, num_selected=3, qk_norm=True, norm_eps=1e-5,
             aux_loss_coef=0.01, z_loss_coef=0.001, attn_impl="flash"),
        _jax_tokens(2, 65, 256), olmoe,
        dict(num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, rope_theta=10000.0, rms_norm_eps=1e-5,
             num_experts_per_tok=3, norm_topk_prob=False, qk_norm=True,
             router_aux_loss_coef=0.01, router_z_loss_coef=0.001),
        params=_olmoe_params),
    # mamba, attention, mamba (of a longer published list); no multiplier
    # 1; one group, as published (its reference norms the gated output
    # whole: several groups, each normed apart, are nemotron's)
    "granite": Row(
        dict(vocab_size=256, embed_dim=64, num_layers=3, num_heads=4,
             num_kv_heads=2, head_dim=16, mlp_dim=96, norm_eps=1e-5,
             layer_types=GRANITE_TYPES, ssm_heads=8, ssm_head_dim=16,
             ssm_state=8, ssm_groups=1, ssm_conv=4, ssm_chunk=8,
             position_embedding="nope", attention_multiplier=0.1,
             embedding_multiplier=3.0, residual_multiplier=0.5,
             logits_scaling=2.0, tie_embeddings=True, max_seq_len=64,
             dtype=jnp.float32, remat=True, attn_impl="flash"),
        _numpy_tokens(2, 41), granite_hybrid,
        {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "shared_intermediate_size": 96,
         "vocab_size": 256, "rms_norm_eps": 1e-5, "num_hidden_layers": 3,
         "layer_types": list(GRANITE_TYPES), "mamba_n_heads": 8,
         "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 1,
         "mamba_d_conv": 4, "mamba_chunk_size": 8,
         "position_embedding_type": "nope", "attention_multiplier": 0.1,
         "embedding_multiplier": 3.0, "residual_multiplier": 0.5,
         "logits_scaling": 2.0, "tie_word_embeddings": True},
        params=functools.partial(seeded, draw=5, also=("D",)),
        precision="highest"),
    # the published pattern (three linear, one full) with a fifth entry
    # that is not run; 96 positions: a chunk of the rule's 64 and a ragged
    # second one
    "olmo_hybrid": Row(
        dict(vocab_size=256, embed_dim=64, num_layers=4, num_heads=4,
             num_kv_heads=4, head_dim=16, mlp_dim=96, norm_eps=1e-6,
             layer_types=OLMO_HYBRID_TYPES, gdn_heads=4, gdn_key_dim=8,
             gdn_value_dim=16, gdn_conv=4, gdn_neg_eigval=True,
             position_embedding="nope", qk_norm=True, block_norm="output",
             max_seq_len=128, dtype=jnp.float32, remat=True,
             attn_impl="flash"),
        _numpy_tokens(2, 97), olmo_hybrid,
        {"num_hidden_layers": 4, "layer_types": list(OLMO_HYBRID_TYPES),
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "linear_num_key_heads": 4, "linear_num_value_heads": 4,
         "linear_key_head_dim": 8, "linear_value_head_dim": 16,
         "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
         "rms_norm_eps": 1e-6},
        params=functools.partial(seeded, draw=5), precision="highest",
        step=Step(optimizer=_ADAM, counters=(GDN_STATE_ABSMAX,),
                  phased=GDN_SCOPES)),
    "lfm2": Row(
        dict(_SMALL, num_layers=8, num_kv_heads=2, dense_mlp_dim=96,
             rope_theta=1e6, norm_eps=1e-5, layer_types=LFM2_PATTERN,
             sconv_width=3, qk_head_norm=True, num_experts=8,
             num_selected=4, topk_norm_eps=1e-6, experts_held=4,
             first_expert=4, leading_dense=2, tie_embeddings=True,
             **_SIGMOID),
        _jax_tokens(2, 33), lfm2_moe,
        dict(layer_types=list(LFM2_PATTERN) + ["conv"] * 4,
             num_hidden_layers=8, num_dense_layers=2, num_attention_heads=4,
             num_key_value_heads=2, rope_theta=1000000, norm_eps=1e-5,
             num_experts_per_tok=4, routed_scaling_factor=1,
             first_expert=4),
        faults=_LFM2_FAULTS, sees=(mean_apart, lfm2_moe.LOSS_RTOL), shares=2,
        step=Step(fields=dict(remat=True), steps=1, counters=_MOE_COUNTERS,
                  named=tuple(f"{scope}/" for scope in SCONV_SCOPES + (
                      "attn_qkv", "moe_experts", "ffn")))),
    "xing4": Row(
        dict(_SMALL, num_layers=4, num_kv_heads=4, dense_mlp_dim=96,
             rope_scaling=XING4_SCALING, num_experts=16, num_selected=4,
             experts_held=4, first_expert=4, shared_experts=1,
             routed_scaling_factor=2.0, leading_dense=2, hc_mult=4,
             num_nextn=1, **_LATENT, **_SIGMOID),
        _jax_tokens(2, 33), xing4,
        dict(first_k_dense_replace=2, hc_mult=4, num_attention_heads=4,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             kv_lora_rank=16, rope_theta=10000, rms_norm_eps=1e-6,
             rope_scaling=XING4_SCALING, num_experts_per_tok=4,
             routed_scaling_factor=2, first_expert=4, hc_sinkhorn_iters=20,
             hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
             mtp_loss_coef=0.3),
        faults=_XING4_FAULTS, sees=(mean_apart, 3e-4), shares=8,
        step=Step(steps=1, counters=("mtp_loss", "moe_held_share",
                                     "moe_dropped"))),
    # the published pattern in small: 1 dense layer then expert layers, 16
    # experts of which this host holds the first 8, 4 a token
    "joyai": Row(
        dict(_SMALL, num_layers=3, num_kv_heads=4, dense_mlp_dim=96,
             rope_theta=32e6, num_experts=16, num_selected=4,
             experts_held=8, first_expert=0, shared_experts=1,
             routed_scaling_factor=2.5, leading_dense=1, num_nextn=1,
             **_LATENT, **_SIGMOID),
        _jax_tokens(4, 33), joyai_flash,
        dict(first_k_dense_replace=1, num_attention_heads=4,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             kv_lora_rank=16, rope_theta=32e6, rms_norm_eps=1e-6,
             num_experts_per_tok=4, routed_scaling_factor=2.5,
             first_expert=0, mtp_loss_coef=0.3),
        faults=_JOYAI_FAULTS, sees=(mean_apart, joyai_flash.LOSS_RTOL),
        shares=2),
    # the published layers in small: M, E, M, *, E; 2 groups of 4 heads; 16
    # experts of width 32 of which this chip holds 4..11, 3 a token; a
    # shared expert of width 48; 8 query heads over 2 KV heads
    "nemotron": Row(
        dict(_NEMOTRON, experts_held=8, num_selected=3,
             routed_scaling_factor=2.5),
        _jax_tokens(4, 33), nemotron_h,
        dict(_NEMOTRON_CONF, num_experts_per_tok=3,
             routed_scaling_factor=2.5),
        params=functools.partial(seeded, also=("D",)), precision="highest",
        faults=_NEMOTRON_FAULTS, sees=(token_rms, 3e-3), shares=8),
    # the same layers with what Nemotron-3 adds: the routed experts in a
    # latent of 16 (a quarter of the hidden size, as published), MORE
    # choices a token (6 of 16) than experts held (4..7), and behind the
    # stack a predicted-ahead module that is a pattern of its own, * then E
    "nemotron3": Row(
        dict(_NEMOTRON, experts_held=4, num_selected=6,
             routed_scaling_factor=5.0, moe_latent=16, num_nextn=1,
             mtp_pattern=NEMOTRON3_MODULE, mtp_loss_coef=0.1),
        _jax_tokens(4, 33), nemotron3,
        dict(_NEMOTRON_CONF, num_experts_per_tok=6, routed_scaling_factor=5,
             mtp_hybrid_override_pattern=NEMOTRON3_MODULE,
             mtp_loss_coef=0.1),
        params=functools.partial(seeded, also=("D",)), precision="highest",
        faults=_NEMOTRON3_FAULTS, sees=(token_rms, 3e-3), shares=4),
    "trinity": Row(
        dict(_SMALL, num_layers=5, num_kv_heads=2, dense_mlp_dim=96,
             rope_theta=1e4, norm_eps=1e-5, layer_types=TRINITY_PATTERN,
             sliding_window=TRINITY_WINDOW, attn_output_gate=True,
             block_norm="sandwich", post_norm_init=0.25,
             position_embedding="rope_windowed", qk_head_norm=True,
             embedding_multiplier=8.0, embed_init_std=0.125, num_experts=16,
             num_selected=2, topk_norm_eps=1e-20, experts_held=4,
             first_expert=4, shared_experts=1, routed_scaling_factor=2.448,
             leading_dense=1, **_SIGMOID),
        _jax_tokens(2, 49), afmoe,
        dict(layer_types=list(TRINITY_PATTERN), num_hidden_layers=5,
             num_dense_layers=1, num_attention_heads=4,
             num_key_value_heads=2, hidden_size=64, rope_theta=10000,
             rms_norm_eps=1e-5, sliding_window=TRINITY_WINDOW,
             num_experts_per_tok=2, route_scale=2.448, first_expert=4,
             mup_enabled=True),
        faults=_TRINITY_FAULTS, sees=(token_max, 1e-3), sound_within=3e-5,
        shares=32,
        step=Step(fields=_KERNELS, steps=1, named=_WINDOWED + (
            "attn_qkv/", "ffn/"), counters=_MOE_COUNTERS + (
                "attn_window_executed_share",))),
    "mellum": Row(
        dict(_SMALL, num_layers=8, num_kv_heads=2, norm_eps=1e-6,
             layer_types=MELLUM_PATTERN, sliding_window=MELLUM_WINDOW,
             position_embedding=ROPE_BY_KIND, rope_parameters=MELLUM_GROUPS,
             num_experts=16, num_selected=4, norm_topk_prob=True,
             experts_held=4, first_expert=4, aux_loss_coef=0.001),
        _jax_tokens(2, 49), mellum,
        dict(layer_types=list(MELLUM_PATTERN), mlp_layer_types=["sparse"] * 8,
             num_hidden_layers=8, num_attention_heads=4,
             num_key_value_heads=2, hidden_size=64, rms_norm_eps=1e-6,
             sliding_window=MELLUM_WINDOW, rope_parameters=MELLUM_GROUPS,
             num_experts_per_tok=4, norm_topk_prob=True, first_expert=4,
             router_aux_loss_coef=0.001),
        faults=_MELLUM_FAULTS, sees=(token_max, 1e-3),
        sound=(dict(num_layers=4), dict(num_hidden_layers=4)),
        sound_within=5e-5, shares=4,
        step=Step(fields=dict(_KERNELS, num_layers=4), steps=1,
                  named=_WINDOWED + ("attn_qkv/rope/",),
                  counters=tuple(mellum.STEP_METRICS))),
    # two published periods, F S S S twice: 4 query heads in a full layer
    # and 6 under the window over 2 KV heads, half a head rotated by YaRN
    # in the full layers, a gate a head, one dense layer then expert layers
    # (16 experts of which this chip holds 4..7, 4 a token x 2.5, a shared
    # expert); two norms a layer
    "laguna": Row(
        dict(_SMALL, num_layers=8, num_kv_heads=2, dense_mlp_dim=96,
             norm_eps=1e-6, layer_types=LAGUNA_PATTERN,
             heads_per_layer=LAGUNA_HEADS, mlp_layer_types=LAGUNA_FFNS,
             sliding_window=LAGUNA_WINDOW, position_embedding=ROPE_BY_KIND,
             rope_parameters=LAGUNA_GROUPS, attn_output_gate="per_head",
             num_experts=16, num_selected=4, experts_held=4, first_expert=4,
             shared_experts=1, routed_scaling_factor=2.5, **_SIGMOID),
        _jax_tokens(2, 49), laguna,
        dict(layer_types=list(LAGUNA_PATTERN),
             mlp_layer_types=list(LAGUNA_FFNS),
             num_attention_heads_per_layer=list(LAGUNA_HEADS),
             num_hidden_layers=8, num_key_value_heads=2, hidden_size=64,
             rms_norm_eps=1e-6, sliding_window=LAGUNA_WINDOW,
             rope_parameters=LAGUNA_GROUPS, num_experts_per_tok=4,
             moe_routed_scaling_factor=2.5, first_expert=4),
        faults=_LAGUNA_FAULTS, sees=(token_max, 1e-3),
        sound=(dict(num_layers=4), dict(num_hidden_layers=4)),
        sound_within=5e-5, shares=8,
        step=Step(fields=dict(_KERNELS, num_layers=4), steps=1,
                  named=_WINDOWED + ("attn_qkv/rope/rope_partial/",
                                     "attn_out/attn_head_gate/", "ffn/"),
                  counters=tuple(laguna.STEP_METRICS))),
    # three layers of one kind: GQA 4/2 with a norm over each head's q and
    # k, an indexer that picks 16 keys a query, every layer an expert layer
    # (16 experts of which this chip holds 4..7, 4 a token, renormalised)
    "keye": Row(
        dict(_SMALL, num_layers=3, num_kv_heads=2, norm_eps=1e-6,
             qk_head_norm=True, rope_theta=1e4, sa_config=KEYE_INDEXER,
             num_experts=16, num_selected=4, norm_topk_prob=True,
             experts_held=4, first_expert=4, aux_loss_coef=0.0,
             max_seq_len=128),
        _jax_tokens(2, 129), keye_sparse,
        dict(num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=2, rope_theta=10000, rms_norm_eps=1e-6,
             sa_config=KEYE_INDEXER, num_experts_per_tok=4,
             norm_topk_prob=True, first_expert=4, idx_loss_coef=1.0,
             router_aux_loss_coef=0.0),
        params=_keye_params, precision="highest", faults=_KEYE_FAULTS,
        sees=(_keye_losses_apart, 1.0), shares=8,
        step=Step(fields=dict(_KERNELS, num_layers=2), named=(
            "dsa_index/", "sparse_scores", "sparse_scores_bwd", "dsa_select/",
            "sparse_select", "sparse_mask", "attention/", "flash_fwd_dsa",
            "flash_dkv_dsa", "dsa_loss/", "attn_out/", "moe_experts/"),
            counters=tuple(keye_sparse.STEP_METRICS))),
    # two layers of one kind under the DENOISING objective: GQA 4/2 with a
    # norm over each head's q and k under the block rule over [noised ;
    # clean] (128 rows of 64 positions), every layer an expert layer (16
    # experts of which this chip holds 4..7, 4 a token, renormalised)
    "sdar": Row(
        dict(_SMALL, num_layers=2, num_kv_heads=2, norm_eps=1e-6,
             qk_head_norm=True, rope_theta=1e4, block_diffusion=SDAR_NOISE,
             num_experts=16, num_selected=4, norm_topk_prob=True,
             experts_held=4, first_expert=4, aux_loss_coef=0.001,
             max_seq_len=128),
        _jax_tokens(2, 65), sdar_block_diffusion,
        dict(num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, rope_theta=10000, rms_norm_eps=1e-6,
             block_diffusion=SDAR_NOISE, num_experts_per_tok=4,
             norm_topk_prob=True, first_expert=4,
             router_aux_loss_coef=0.001),
        precision="highest",
        step=Step(fields=_KERNELS, learns=False, named=(
            "(bd_noise)/", "attention/", "flash_fwd_bd", "flash_dkv_bd",
            "moe_experts/"),
            counters=tuple(sdar_block_diffusion.STEP_METRICS))),
    # the published pattern in small: 1 dense layer then expert layers, KDA
    # x3 to one latent layer WITHOUT a q rank or a rotation; 16 experts of
    # which this chip holds 4..7, 4 a token, a shared expert; 96 positions:
    # a chunk of the rule's 64 and a ragged second one
    "kimi": Row(
        dict(_SMALL, num_layers=5, num_kv_heads=4, dense_mlp_dim=96,
             norm_eps=1e-5, linear_attn_config=KIMI_LINEAR,
             **dict(_LATENT, q_lora_rank=None), position_embedding="nope",
             num_experts=16, num_selected=4, experts_held=4, first_expert=4,
             shared_experts=1, routed_scaling_factor=2.446, leading_dense=1,
             max_seq_len=128, **_SIGMOID),
        _jax_tokens(2, 97), kimi_linear,
        dict(linear_attn_config=KIMI_LINEAR, num_hidden_layers=5,
             first_k_dense_replace=1, q_lora_rank=None, mla_use_nope=True,
             num_expert_group=1, num_attention_heads=4, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=16,
             rms_norm_eps=1e-5, num_experts_per_token=4,
             routed_scaling_factor=2.446, first_expert=4),
        precision="highest", faults=_KIMI_FAULTS,
        sees=(mean_apart, kimi_linear.LOSS_RTOL), shares=16,
        step=Step(fields=_KERNELS, optimizer=_ADAM, phased=KDA_SCOPES,
                  counters=(KDA_STATE_ABSMAX, KDA_CHUNK_DECAY_MIN,
                            "moe_dropped"))),
    # one published period, G K K K: a gated NoPE softmax layer of 4 query
    # / 2 KV heads, then three KDA layers whose write strength reaches 2,
    # their inner width (4 x 16) TWICE the hidden size as published; every
    # layer an expert layer, 16 experts of which this chip holds 4..7, 4 a
    # token, a shared expert; 96 positions as Kimi-Linear's row
    "solar": Row(
        dict(_SMALL, embed_dim=32, num_layers=4, num_kv_heads=2,
             norm_eps=1e-5, gqa_layers=SOLAR_GQA,
             linear_attn_config=SOLAR_LINEAR, kda_neg_eigval=True,
             attn_output_gate=True, position_embedding="nope",
             num_experts=16, num_selected=4, experts_held=4, first_expert=4,
             shared_experts=1, max_seq_len=128, **_SIGMOID),
        _jax_tokens(2, 97), solar_open2,
        dict(gqa_layers=list(SOLAR_GQA), linear_attn_config=SOLAR_LINEAR,
             num_hidden_layers=4, first_k_dense_replace=0, use_rope=False,
             use_gqa_gate=True, kda_use_full_proj=False,
             kda_allow_neg_eigval=True, num_attention_heads=4,
             num_key_value_heads=2, rms_norm_eps=1e-5,
             num_experts_per_tok=4, routed_scaling_factor=1, first_expert=4),
        precision="highest", faults=_SOLAR_FAULTS,
        sees=(mean_apart, solar_open2.LOSS_RTOL), shares=32,
        step=Step(fields=_KERNELS, optimizer=_ADAM, counters=(
            KDA_BETA_MAX, *solar_open2.STEP_METRICS))),
    # the SambaY rule at depth 8, M W M W | M F | G C: a state of 4 numbers
    # a channel over 128 channels, 4 query / 2 KV heads (2 pairs on 1), a
    # window of 8 of the 64 tokens, LayerNorms and biases throughout; D away
    # from 1.  Depth 12 (two units, two cross layers) is the same row under
    # ``num_layers=12``
    "phi4flash": Row(
        dict(vocab_size=128, embed_dim=64, num_layers=8, num_heads=4,
             num_kv_heads=2, head_dim=16, mlp_dim=96, norm_eps=1e-5,
             mb_per_layer=2, sliding_window=8, s6_state=4, s6_conv=4,
             s6_expand=2, norm_type="layernorm", attn_bias=True,
             position_embedding="nope", tie_embeddings=True, max_seq_len=64,
             dtype=jnp.float32, remat=False, attn_impl="reference"),
        _jax_tokens(2, 65), phi4flash,
        dict(num_hidden_layers=8, num_attention_heads=4,
             num_key_value_heads=2, sliding_window=8, mamba_d_state=4,
             layer_norm_eps=1e-5, mb_per_layer=2),
        params=functools.partial(seeded, also=("s6_D",)),
        precision="highest", faults=_PHI4FLASH_FAULTS,
        sees=(token_rms, 1e-3),
        step=Step(fields=_KERNELS, named=(
            "s6_in/", "s6_conv/", "s6_scan/", "s6_out/", "gmu/", "attn_qkv/",
            "attention/", "flash_fwd_win", "flash_dkv_win", "flash_fwd",
            "attn_diff/", "attn_out/", "ffn/"),
            counters=tuple(phi4flash.STEP_METRICS))),
    # ONE stack of two layers run four times over the same weights, four
    # norms a layer, the last norm after every pass, an exit gate and the
    # head read after every pass
    "ouro": Row(
        dict(_SMALL, num_layers=2, num_kv_heads=4, norm_eps=1e-6,
             rope_theta=1e4, block_norm="sandwich", looped=OURO_LOOP),
        _jax_tokens(2, 65), ouro_looped,
        dict(num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, rope_theta=10000, rms_norm_eps=1e-6,
             total_ut_steps=4, looped=dict(OURO_LOOP)),
        params=_ouro_params, precision="highest"),
}


def looped(passes: int, entropy_coef: float = 0.05):
    """``(the program's field, the reference's keys)`` of an Ouro row at
    ``passes`` passes, both hashable."""
    group = {"passes": passes, "entropy_coef": entropy_coef}
    return tuple(sorted(group.items())), dict(
        total_ut_steps=passes, looped=Frozen(group))


def tiny(name: str, **kw) -> LlamaConfig:
    """The row's configuration with ``kw`` in place of its fields."""
    return LlamaConfig.tiny(**{**ROWS[name].fields, **kw})


def apart(ours, theirs):
    """Leaf by leaf: the largest difference over the reference's largest
    entry."""
    return jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), ours, theirs)


class Side(NamedTuple):
    """The program on a row's tokens, each function compiled at its first
    call: ``value_and_grad(params) -> ((total, parts), gradients)``, ONE
    program for the loss, its parts and the gradients; ``loss(params) ->
    (total, parts)`` and ``token_nll(params)``, a forward pass each (folded
    into the gradients' program the second forward pass is not merged with
    the first, and compiles dearer than alone)."""
    cfg: LlamaConfig
    params: Any
    value_and_grad: Callable
    loss: Callable
    token_nll: Callable


class Wanted(NamedTuple):
    """What the plain reference says of a row's parameters: its ``parts``
    (``total``, ``token_nll`` and its own among them) and the gradients of
    its total."""
    parts: Dict[str, Any]
    grads: Any


def _at(name, fn):
    """``jit(fn)``, traced and run at the matmul precision both of a row's
    sides run under."""
    jitted = jax.jit(fn)
    precision = ROWS[name].precision

    def call(*args):
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            return jitted(*args)

    return call


def side_of(name: str, cfg: LlamaConfig, params) -> Side:
    """The program's side of a row under ANY configuration (one a test
    changed by hand) on the row's tokens; nothing is kept: what it traces
    follows a patch the caller put in."""
    row = ROWS[name]
    batch = {"tokens": row.tokens}

    def token_nll(params):
        logits, _ = forward(params, row.tokens[:, :-1], cfg)
        return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                    row.tokens[:, 1:, None], -1)[..., 0]

    return Side(
        cfg, params,
        _at(name, jax.value_and_grad(lambda p: loss_fn(p, batch, cfg),
                                     has_aux=True)),
        _at(name, lambda p: loss_fn(p, batch, cfg)), _at(name, token_nll))


@functools.lru_cache(maxsize=None)
def _program(name: str, overrides: Tuple) -> Side:
    cfg = tiny(name, **dict(overrides))
    return side_of(name, cfg, ROWS[name].params(cfg))


def program(name: str, **kw) -> Side:
    """The program's side of a row under ``kw`` (hashable values): the
    same object, its compiled functions with it, for every test of a
    process that asks for it."""
    return _program(name, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _reference(name: str, conf: Tuple, overrides: Tuple) -> Wanted:
    row = ROWS[name]
    conf = {**row.conf, **dict(conf)}

    def loss(params):
        parts = row.reference.loss_parts(params, row.tokens, conf)
        return parts["total"], parts

    (_, parts), grads = _at(name, jax.value_and_grad(loss, has_aux=True))(
        _program(name, overrides).params)
    return Wanted(parts, grads)


def reference(name: str, conf=(), **kw) -> Wanted:
    """The plain reference on ``program(name, **kw).params`` (the same draw
    whatever the attention or the checkpoint), ``conf`` (a dict) in place
    of its configuration's keys: ONE program, run once a process — and so
    asked for BEFORE a test patches anything the reference could read."""
    return _reference(name, tuple(sorted(dict(conf).items())),
                      tuple(sorted(kw.items())))


def against_the_reference(name: str, *, parts=("loss",), rtol=2e-5,
                          nll_atol=3e-5, grad_rtol=1e-4, conf=None, dead=(),
                          **kw):
    """The test every model repeats: the program's total, the named parts,
    each token's loss and every gradient leaf beside the reference's, on
    the row's parameters.  ``kw`` as ``program`` takes it (the flash
    kernels, the checkpoint); ``conf``: the reference's keys that follow a
    ``kw`` which changes the parameters' SHAPES (the reference then runs on
    that program's draw); ``dead``: tensors the loss does not depend on,
    whose gradients are rounding on both sides and held to that.  Returns
    ``(total, parts, want, gradients)`` for what a model asserts beyond."""
    ours, (want, want_grads) = program(name, **kw), (
        reference(name, conf, **kw) if conf else reference(name))
    params = ours.params
    (total, got), grads = ours.value_and_grad(params)
    np.testing.assert_allclose(total, want["total"], rtol=rtol)
    for part in parts:
        np.testing.assert_allclose(got[part], want[part], rtol=rtol,
                                   err_msg=part)
    if nll_atol is not None:
        np.testing.assert_allclose(ours.token_nll(params),
                                   want["token_nll"], atol=nll_atol)
    worst = apart(grads, want_grads)
    for path, leaf in jax.tree_util.tree_leaves_with_path(worst):
        if path[-1].key not in dead:
            assert leaf < grad_rtol, (path, worst)
    for side in (grads, want_grads):
        for path, leaf in jax.tree_util.tree_leaves_with_path(side):
            assert path[-1].key not in dead or float(
                jnp.max(jnp.abs(leaf))) < 1e-6, path
    return total, got, want, grads


# -- a changed part stands apart from the reference ----------------------------

def fault_ids(name: str, group: str = "part"):
    """The ids of a row's faults, for a model's file to parametrise over."""
    return [f.id for f in ROWS[name].faults if f.group == group]


class Apart(NamedTuple):
    """What ``stands_apart`` read: the fault's distance, and the changed
    side, its parameters and the reference's answer for what a model's
    file asserts beyond (under no patch any more)."""
    distance: float
    side: Side
    params: Any
    want: Wanted


def _hashable(fields) -> bool:
    try:
        hash(tuple(fields.values()))
    except TypeError:
        return False
    return True


def stands_apart(name: str, fault_id: str) -> Apart:
    """The test every model repeats over its row's ``faults``: what each
    part is worth to the loss.  The program with the part got wrong stands
    apart from the reference — by the fault's ``distance`` or the row's —
    by more than the check's tolerance (``over``), or the check could not
    see that part.  The reference's answer is asked for before anything is
    patched; a fault of fields alone is ``program(name, **fields)``, so two
    cases of a file that change the same field share a compile; under a
    patch the changed program is traced anew and nothing of it is kept."""
    row = ROWS[name]
    fault = next(f for f in row.faults if f.id == fault_id)
    base, conf = row.sound
    sound = program(name, **base)
    want = reference(name, conf, **base) if base else reference(name)
    if row.sound_within is not None:
        assert token_max(sound, sound.params, want) < row.sound_within
    fields = {**base, **fault.fields}
    distance = fault.distance or row.sees[0]
    with pytest.MonkeyPatch.context() as patch:
        if fault.patch is not None:
            fault.patch(patch)
        if fault.patch is None and _hashable(fields):
            side = program(name, **fields)
        else:
            cfg = tiny(name, **fields)
            side = side_of(name, cfg, sound.params if not fault.own_draw
                           else row.params(cfg))
        params = side.params if fault.own_draw else sound.params
        if fault.reread is not None:
            params = fault.reread(params, side.cfg)
        reading = distance(side, params, want)
    if fault.under is not None:
        assert reading < fault.under, (fault_id, reading)
    else:
        over = row.sees[1] if fault.over is None else fault.over
        assert reading > over, (fault_id, reading, over)
    return Apart(reading, side, params, want)


# -- one expert layer and the shares of it (the models' share tests) ----------

def expert_layer(tokens=96, d=64, m=32, experts=32, seed=3):
    """The tensors of ONE sigmoid-routed expert layer with a selection bias
    and a shared expert, uncut, and ``tokens`` rows ``x`` to run it on."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    normal = jax.random.normal
    return dict(
        x=normal(keys[0], (tokens, d)),
        mlp_norm=1.0 + 0.3 * normal(keys[1], (d,)),
        router=normal(keys[2], (d, experts)) * d ** -0.5,
        router_bias=0.05 * normal(keys[3], (experts,)),
        w_gate=normal(keys[4], (experts, d, m)) * d ** -0.5,
        w_up=normal(keys[5], (experts, d, m)) * d ** -0.5,
        w_down=normal(keys[6], (experts, m, d)) * m ** -0.5,
        shared_gate=normal(keys[7], (d, m)) * d ** -0.5,
        shared_up=normal(keys[8], (d, m)) * d ** -0.5,
        shared_down=normal(keys[9], (m, d)) * m ** -0.5)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def share(p, first, held, k, scale):
    """What the chip that holds experts ``first .. first + held - 1`` of
    ``expert_layer``'s adds for its tokens (the routed part alone), and the
    layer's statistics: ``moe_block`` routing over ALL the experts, ``k`` a
    token, gates renormalised over the chosen times ``scale``."""
    return moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=k, norm_topk_prob=True, scoring="sigmoid",
        select_bias=p["router_bias"], gate_scale=scale, first_expert=first,
        residual=False)


def shares_add_up(name: str, p, share_of, whole, chosen=None, *, k,
                  shared=0.0, atol=2e-5):
    """The test every model with a held share repeats: ``ROWS[name].shares``
    chips with an equal cut of ``p``'s experts each
    (``share_of(p, first, held) -> (routed part, statistics)``): their
    routed parts, and ``shared`` (the shared expert, ONCE), are ``whole``,
    the uncut layer as the reference has it; the held shares sum to 1,
    nothing is dropped, and every share routes over ALL the experts and
    counts the same ``k`` assignments a token (the reference's ``chosen``
    where it hands them out).  Returns the parts for what a model asserts
    beyond."""
    experts = p["router"].shape[1]
    held = experts // ROWS[name].shares
    parts = [share_of(p, first, held) for first in range(0, experts, held)]
    assert len(parts) == ROWS[name].shares
    np.testing.assert_allclose(sum(y for y, _ in parts) + shared, whole,
                               atol=atol)
    stats = [s for _, s in parts]
    assert sum(float(s["held_share"]) for s in stats) == pytest.approx(1.0)
    assert all(float(s["dropped"]) == 0.0 for s in stats)
    counts = stats[0]["counts"] if chosen is None else np.bincount(
        np.asarray(chosen).ravel(), minlength=experts)
    for s in stats:
        np.testing.assert_array_equal(s["counts"], counts)
    assert int(np.sum(counts)) == p["x"].shape[0] * k
    return parts


# -- the train step reports its scopes and counters ----------------------------

class Stepped(NamedTuple):
    """What ``train_step_reports`` built and ran, for what a model asserts
    beyond: the configuration, the state before the first step (and its
    parameters as numpy arrays), the lowered step's text, the compiled
    step, the state and the metrics after the last step."""
    cfg: LlamaConfig
    initial: Any
    before: Any
    text: str
    compiled: Any
    state: Any
    metrics: Dict[str, Any]


def train_step_reports(name: str) -> Stepped:
    """The test every model repeats over its row's ``step``: the train step
    is traced ONCE and compiled once; its lowered text holds every name in
    ``named``; ``steps`` steps run, every loss is finite and — over several
    steps, unless ``learns`` says no — the last is under the first; the
    metrics hold ``counters``; the compiled program's ops carry each scope
    in ``phased`` in the forward, the rematerialised and the backward
    pass."""
    spec = ROWS[name].step
    cfg, opt = tiny(name, **spec.fields), spec.optimizer()
    initial = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    before = jax.tree.map(np.asarray, initial.params)
    batch = {"tokens": ROWS[name].tokens}
    lowered = make_train_step(cfg, opt, donate=False).lower(initial, batch)
    text = lowered.as_text(debug_info=True)
    for scope in spec.named:
        assert scope in text, scope
    compiled = lowered.compile()
    state, losses = initial, []
    for _ in range(spec.steps):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    if spec.steps > 1 and spec.learns:
        assert losses[-1] < losses[0], losses
    assert set(spec.counters) <= set(metrics)
    if spec.phased:
        seen = {scope_and_phase(n, STEP_SCOPES) for n in re.findall(
            r'op_name="([^"]*)"', compiled.as_text())}
        assert {(s, phase) for s in spec.phased for phase in (
            "forward", "remat", "backward")} <= seen
    return Stepped(cfg, initial, before, text, compiled, state, metrics)
