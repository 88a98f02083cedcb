"""Flagship model family: Llama-style decoder LM, TPU-first.  This file is
the DECODER: configuration, parameter tree, embedding, the scan over layers
under the layer checkpoint, head, predicted-ahead module, loss, pipeline
entry points.  What a layer is made of lives in ``models/blocks/``: a MIXER
(``blocks.MIXERS``: softmax attention, over every earlier token, over a
window of them or over the keys a learned indexer picks | latent attention |
a Mamba-2
state-space mixer | a gated delta-rule linear-attention mixer, its decay
a number a head or a vector over the key channels | a gated short
convolution) followed by an FFN (``blocks.FFNS``: dense, SwiGLU or
ungated relu^2 | dropless experts with or without a shared expert) —
either of the two may be the empty block (``none``), so a layer can be a
mixer OR an FFN alone —, each on a RESIDUAL
(``blocks/residual.py``: one stream | ``hc_mult`` streams mixed round every
block by learned doubly stochastic maps).  A model is a pattern of such
layers (``layer_types``, ``leading_dense``; ``layer_pattern``, a character
a layer; ``linear_attn_config``, the layers of each mixer by number; or
``gqa_layers``, the softmax layers by number among ``kda`` ones),
with or without a predicted-ahead module behind them — one more layer of
the model's last kind, or, behind a ``layer_pattern`` model, a pattern of
its own (``mtp_pattern``: a character a layer, each ONE sub-block on the
residual as the model's are, so the module is as many scans as it has runs).
Each block declares its own tensors, initialisers, saved residuals, scopes
and step statistics (``blocks.base.Block``); the decoder reads those and
names no mixer.

Pure-functional design: params are a pytree of arrays, every tensor
dimension has a *logical axis name*, and one rules table
(``parallel.sharding.DEFAULT_RULES``) maps names to mesh axes — so the same
model runs DP, FSDP, 2D (fsdp x tp), MoE-EP, or sequence-parallel by
swapping rules, never editing model code.

The decoder's TPU-first choices:
- layers are *stacked* on a leading "layer" dim and driven by ``lax.scan``
  (+``jax.checkpoint``): one trace/compile of a single layer regardless of
  depth, rematerialized backward to trade FLOPs for HBM but for the few
  residuals the blocks name (``_checkpoint``).
- a model whose layers differ is scanned by maximal RUNS of one kind
  (mixer, FFN), each run one ``lax.scan`` over its own stacked parameters,
  which hold only what that kind has (``params["layers"]`` is then a tuple
  of stacks, one a run: LFM2-8B-A1B is five scans at depth 8); a model of
  one kind is one run and ``params["layers"]`` the one stack.
- bf16 activations/params with f32 RMSNorm stats and f32 logits/loss — the
  MXU-native recipe.
- where a block's RMSNorm sits is the model's (``block_norm``): ``x +
  f(norm(x))``, or the OLMo 2 family's ``x + norm(f(x))``.

Reference counterpart: none in Ray core (no tensor ops); RLlib's model zoo
(``rllib/models/catalog.py``) plays the "models shipped with the framework"
role, and its JAX support is a 299-LoC stub (``rllib/models/jax/``) — cited
for parity, not design.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.blocks import FFNS, MIXERS, residual
from ray_tpu.models.blocks.base import (
    BIAS_STD, Ctx, Param, normal, ones, small)
from ray_tpu.models.blocks.residual import (
    from_streams, hc_block, scaled, to_streams)
from ray_tpu.ops.layers import rms_norm, rope_type
from ray_tpu.ops.moe import update_selection_bias
from ray_tpu.parallel.mesh import AXIS_SP
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalAxisRules, logical_to_mesh_axes, manual_shard_map,
    with_logical_constraint,
)


# A layer of ONE sub-block on the residual, by the character the public
# files of such models give it (``hybrid_override_pattern``): the (mixer,
# FFN) pair it is, the absent half the empty block.
LAYER_PATTERN = {"M": ("mamba", "none"), "E": ("none", "moe"),
                 "*": ("attention", "none"), "-": ("none", "dense")}


# ``position_embedding``: rotary positions in every layer, the tables by
# the layer's kind; the key of ``rope_parameters`` a layer without (False)
# or with (True) a window reads; and the mixers that rotate at all.
ROPE_BY_KIND = "rope_by_layer_type"
ROPE_KINDS = {False: "full_attention", True: "sliding_attention"}
ROTARY_MIXERS = ("attention", "full_attention", "sliding_attention", "latent",
                 "indexed")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    embed_dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    mlp_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"          # flash | ring | ulysses | reference
    num_experts: int = 0              # 0 = dense FFN
    num_selected: int = 2             # experts a token goes to (all of them)
    norm_topk_prob: bool = False      # renormalise the selected gates
    topk_norm_eps: float = 0.0        # ... over their sum plus this
    aux_loss_coef: float = 0.01       # load-balancing loss
    z_loss_coef: float = 0.0          # router z-loss
    norm_eps: float = 1e-6            # every RMSNorm
    qk_norm: bool = False             # RMSNorm over the q and k projections
    qk_head_norm: bool = False        # ... over EACH head's q and k instead
    remat: bool = True
    # The mixer of each layer, "attention" (or "full_attention") |
    # "sliding_attention" | "mamba" | "linear_attention" | "kda" | "conv";
    # only the first ``num_layers`` entries are the model, empty =
    # attention everywhere.
    layer_types: Tuple[str, ...] = ()
    # What a "sliding_attention" layer's query sees: itself and the
    # sliding_window - 1 tokens before it.
    sliding_window: int = 0
    # o * sigmoid(h W_g) before W_o: True, a gate a head AND channel (W_g
    # as wide as W_q); "per_head", ONE number a head (W_g (d, heads))
    attn_output_gate: Any = False
    # The public file's ``num_attention_heads_per_layer``: a layer's count
    # of QUERY heads, one entry a layer and ONE count a KIND of layer
    # (``q_heads``: a "sliding_attention" layer's, every other's); empty:
    # ``num_heads`` everywhere.  The KV heads and a head's size are the
    # model's.
    heads_per_layer: Tuple[int, ...] = ()
    ssm_heads: int = 0                # Mamba-2: heads x head_dim = inner width
    ssm_head_dim: int = 64
    ssm_state: int = 128              # state size a head (d_state)
    ssm_groups: int = 1               # groups that share B and C
    ssm_conv: int = 4                 # width of the causal depthwise conv
    ssm_chunk: int = 256              # tokens a chunk of the scan
    # rope | nope (no position signal) | rope_windowed (``rotary``: RoPE
    # in the "sliding_attention" layers, none in the others) |
    # rope_by_layer_type (RoPE in every layer, by the rule ``rope_parameters``
    # gives the layer's KIND: ``rope_rule``)
    position_embedding: str = "rope"
    attention_multiplier: Optional[float] = None  # None: head_dim ** -0.5
    embedding_multiplier: float = 1.0  # on the embedded tokens
    # std an UNTIED embedding table is drawn at (0: 1).  A model that
    # multiplies its embeddings by sqrt(d) draws them at about 1 / sqrt(d).
    embed_init_std: float = 0.0
    residual_multiplier: float = 1.0  # on what each block adds to the stream
    logits_scaling: float = 1.0       # logits are divided by it
    tie_embeddings: bool = False      # the head reads the embedding table
    # Latent attention: kv_lora_rank > 0 makes "latent" the mixer of a
    # model without layer_types.  A head's q and k are qk_nope_dim +
    # qk_rope_dim wide, its v and output v_head_dim.
    q_lora_rank: Optional[int] = 0    # 0 or None: q is ONE matrix, ``wq``
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Any = None          # the public file's YaRN group
    # The public file's rotary rule BY KIND of layer: {"full_attention":
    # {"rope_type", "rope_theta", and of a "yarn" one "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "attention_factor"}, "sliding_attention": {...}}.  Read under
    # position_embedding="rope_by_layer_type", in place of ``rope_theta``
    # and ``rope_scaling``.  A kind's "partial_rotary_factor" is the share
    # of a head, from its first dimension on, that the kind's layers rotate
    # (``rotary_dim``; the frequencies are reckoned over that width, the
    # rest of the head passes through).
    rope_parameters: Any = None
    # Expert layers: mlp_dim is an expert's width.
    leading_dense: int = 0            # layers 0.. with a dense FFN instead
    # ... or each layer's FFN as a public file spells it, one entry a
    # layer, "dense" | "sparse" (the expert layer); only the first
    # ``num_layers`` are the model.  In place of ``leading_dense``.
    mlp_layer_types: Tuple[str, ...] = ()
    dense_mlp_dim: int = 0            # their width (0: mlp_dim)
    experts_held: int = 0             # of num_experts, here (0: all)
    first_expert: int = 0             # the first one held
    shared_experts: int = 0           # experts every token meets
    # The width the ROUTED experts work in (the public files'
    # ``moe_latent_size``), between a projection down before the dispatch
    # and one up after the combine; 0: the model's own, no projection.
    moe_latent: int = 0
    router_scoring: str = "softmax"   # softmax | sigmoid
    topk_method: str = "greedy"       # noaux_tc: a selection bias
    router_groups: int = 1            # group-limited routing: 1 = none
    routed_scaling_factor: float = 1.0
    bias_update_speed: float = 0.001  # of the selection bias, a step
    # The residual: hc_mult streams (1: the plain one).
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    num_nextn: int = 0                # predicted-ahead modules (0 | 1)
    mtp_loss_coef: float = 0.3
    # The module's own layers where the public file spells them
    # (``mtp_hybrid_override_pattern``; ``LAYER_PATTERN``, every character
    # is run): what a ``layer_pattern`` model's module is made of.  Empty:
    # one layer of the model's last mixer and its expert (or dense) FFN.
    mtp_pattern: str = ""
    # The gated delta-rule mixer: gdn_heads heads whose q and k are
    # gdn_key_dim wide and whose v and output gdn_value_dim.
    gdn_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4                 # width of the causal depthwise conv
    gdn_neg_eigval: bool = False      # beta in (0, 2): eigenvalues (-1, 1)
    # The public file's group of a model whose linear-attention layers
    # have a decay PER KEY CHANNEL (mixer ``kda``) beside latent-attention
    # ones: {"kda_layers": [...], "full_attn_layers": [...]} (layers counted
    # from 1; entries past ``num_layers`` name layers that are not run),
    # "num_heads", "head_dim" (keys and values alike),
    # "short_conv_kernel_size".  It names every layer's mixer where
    # ``layer_types`` names none (with ``layer_types`` the sizes alone are
    # read).
    linear_attn_config: Any = None
    # Of a model whose public file lists its SOFTMAX layers instead, counted
    # from 0 (``gqa_layers``; entries past ``num_layers`` name layers that
    # are not run): those are ``attention``, every other layer ``kda`` at
    # ``linear_attn_config``'s sizes.
    gqa_layers: Tuple[int, ...] = ()
    kda_neg_eigval: bool = False      # beta in (0, 2): eigenvalues (-1, 1)
    sconv_width: int = 3              # taps of the gated short convolution
    # Learned sparse attention, the public file's group: {"indexer_num_heads",
    # "indexer_head_dim", "indexer_num_kv_heads" (1: the index heads share
    # ONE key), "topk": the keys a query reads; "q_chunk_size" and
    # "kv_chunk_size", a release kernel's tiles, change no value and are not
    # read}.  With it every "attention" layer is the mixer ``indexed``: an
    # indexer scores the causal pairs, the softmax runs over a query's
    # ``topk`` highest alone, and the indexer's own loss (the KL from the
    # heads' mean attention over the selection to the softmax of its scores
    # there) enters the step's at ``idx_loss_coef``.
    sa_config: Any = None
    idx_loss_coef: float = 1.0
    # A model trained by BLOCK DIFFUSION (arXiv:2503.09573), the group:
    # {"block_length": B, "mask_token_id", "eps", "noise_seed"}.  With it the
    # objective is denoising, not next-token (``_denoising_streams``): a
    # sequence's tokens are replaced by the mask token with probability
    # ``p = (1 - eps) t + eps``, ``t ~ U(0, 1)`` a sequence, the model runs
    # ONE pass over ``[noised ; clean]`` (2 L rows a sequence, both halves at
    # positions 0..L-1) in which every "attention" layer is the mixer
    # ``block_attention`` — bidirectional inside a block of B positions,
    # causal across blocks, the noised stream reading the clean past — and
    # the loss is the masked positions' OWN tokens' cross-entropy over
    # ``p``, read off the noised half.  ``forward`` is that pass at step 0.
    block_diffusion: Any = None
    # A LOOPED model (arXiv:2510.25741), the group {"passes": T,
    # "entropy_coef": beta}: the WHOLE stack runs T times over the same
    # weights, the model's last norm at the end of EVERY pass (what it hands
    # on is what the next pass starts from), and after every pass an exit
    # gate (one number a token) and the head are read.  The loss is the exit
    # distribution's expected next-token loss less ``beta`` times its
    # entropy (``_exit_mixture``); ``forward`` returns the LAST pass's
    # logits.  None: the stack runs once and nothing here is traced.
    looped: Any = None
    # Where a block's RMSNorm sits: "input", x + f(norm(x)); "output",
    # x + norm(f(x)) with the same weight on what the block adds; or
    # "sandwich", x + post_norm(f(norm(x))): two norms a block.
    block_norm: str = "input"
    # The gain a "sandwich" model's second norms start at: where they
    # start at 1 every block hands on unit RMS whatever it computed, and
    # what a random attention layer adds to every token alike (the mean
    # of its values) is as large as the token's own part.
    post_norm_init: float = 1.0
    # A model whose layers are ONE sub-block each, as its public file
    # spells them (``LAYER_PATTERN``: a character a layer; only the first
    # ``num_layers`` are the model).  Empty: ``layer_types`` says the mixers.
    layer_pattern: str = ""
    ffn_act: str = "swiglu"           # swiglu | relu2 (ungated: two matrices)
    shared_mlp_dim: int = 0           # the shared expert's width (0:
    #                                   shared_experts x mlp_dim)
    # Initialisation.  The public files' ``rescale_prenorm_residual``, as
    # Megatron-LM's ``scaled_init_method_normal`` draws a model's output
    # layers: every projection that writes to the residual at
    # 1/sqrt(2 x depth) of the rest, the depth the PUBLISHED model's
    # whatever part of it is run (``published_layers``; 0: ``num_layers``).
    rescale_prenorm_residual: bool = False
    published_layers: int = 0
    select_bias_init: float = 0.02    # std the selection bias is drawn at
    # A SambaY decoder (arXiv:2507.06607; the public ``mb_per_layer``, 2:
    # every second layer a recurrent one).  With it the mixers follow from
    # the depth L alone (``sambay_mixers``): even layers up to L/2 are
    # Mamba-1 (``mamba1``), the odd ones below L/2 differential attention
    # under ``sliding_window``, layer L/2 + 1 in full — the layer whose keys
    # and values the odd layers after it attend over (``diff_cross``) — and
    # the even layers after it gated memory units (``gmu``) on the scan
    # output of layer L/2.
    mb_per_layer: int = 0
    s6_state: int = 16                # Mamba-1: state numbers a channel
    s6_conv: int = 4                  # width of its causal depthwise conv
    s6_expand: int = 2                # inner width over the model's
    s6_dt_rank: Any = "auto"          # "auto": ceil(embed_dim / 16)
    # rmsnorm | layernorm (every block's norm and the last one with a
    # bias, ``norm_eps`` its epsilon)
    norm_type: str = "rmsnorm"
    attn_bias: bool = False           # biases on q, k, v and the output

    def __post_init__(self):
        # a configuration file hands a list: keep the config hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        object.__setattr__(self, "heads_per_layer",
                           tuple(self.heads_per_layer))
        object.__setattr__(self, "mlp_layer_types",
                           tuple(self.mlp_layer_types))
        if self.mlp_layer_types and (
                set(self.mlp_layer_types) - {"dense", "sparse"}
                or len(self.mlp_layer_types) < self.num_layers
                or self.leading_dense or self.layer_pattern
                or not self.num_experts):
            raise ValueError(
                "mlp_layer_types names every layer's FFN, 'dense' or "
                f"'sparse', num_layers ({self.num_layers}) of them or more, "
                "of a model with experts, in place of leading_dense and "
                f"layer_pattern: {self.mlp_layer_types}")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config", tuple(sorted(
                (k, tuple(v) if isinstance(v, list) else v)
                for k, v in self.linear_attn_config.items())))
        if isinstance(self.sa_config, dict):
            object.__setattr__(self, "sa_config",
                               tuple(sorted(self.sa_config.items())))
        if self.sa_config and (
                self.index_group.get("indexer_num_kv_heads", 1) != 1
                or min(self.index_heads, self.index_dim, self.index_topk) < 1
                or self.index_dim % 2 or self.kv_lora_rank
                or self.hc_mult > 1):
            raise NotImplementedError(
                "sa_config: an indexer of indexer_num_heads x "
                "indexer_head_dim (even) against ONE key head "
                "(indexer_num_kv_heads 1) that picks topk keys a query, on "
                f"softmax attention over one stream: {self.index_group}")
        if isinstance(self.block_diffusion, dict):
            object.__setattr__(self, "block_diffusion",
                               tuple(sorted(self.block_diffusion.items())))
        if self.block_diffusion:
            self._check_block_diffusion()
        if isinstance(self.looped, dict):
            object.__setattr__(self, "looped",
                               tuple(sorted(self.looped.items())))
        if isinstance(self.rope_parameters, dict):
            # (a public file may repeat a number beside the kinds' groups:
            # it is kept as it stands and no kind reads it)
            object.__setattr__(self, "rope_parameters", tuple(sorted(
                (kind, tuple(sorted(group.items()))
                 if isinstance(group, dict) else group)
                for kind, group in self.rope_parameters.items())))
        if self.router_groups != 1 or self.num_nextn > 1:
            raise NotImplementedError(
                "group-limited routing (n_group > 1) and more than one "
                "predicted-ahead module are not implemented")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring {self.router_scoring!r}")
        if self.block_norm not in ("input", "output", "sandwich"):
            raise ValueError(f"block_norm {self.block_norm!r}")
        if self.position_embedding not in ("rope", "nope", "rope_windowed",
                                           ROPE_BY_KIND):
            raise ValueError(
                f"position_embedding {self.position_embedding!r}")
        if self.block_norm == "output" and (
                self.num_experts or {"mamba", "conv"} & set(self.layer_types)):
            raise NotImplementedError(
                "block_norm='output' is implemented for the softmax, latent "
                "and delta-rule mixers and the dense FFN (not the expert "
                "layer, which norms its input inside); 'sandwich' for the "
                "softmax and latent mixers, the dense FFN and the expert "
                "layer")
        if self.block_norm == "sandwich" and (
                {"mamba", "conv", "linear_attention"} & set(self.layer_types)
                or self.layer_pattern):
            raise NotImplementedError(
                "block_norm='sandwich' is implemented for the softmax and "
                "latent mixers, the dense FFN and the expert layer")
        if "sliding_attention" in self.layer_types and (
                self.sliding_window < 1):
            raise ValueError(
                "a sliding_attention layer needs sliding_window, the keys a "
                f"query sees: {self.sliding_window}")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm is over the whole projection, "
                             "qk_head_norm over each head: one of the two")
        if self.attn_output_gate not in (False, True, "per_head"):
            raise ValueError(
                f"attn_output_gate {self.attn_output_gate!r}: True (a gate "
                "a head and channel) or 'per_head' (one number a head)")
        if self.ffn_act not in ("swiglu", "relu2"):
            raise ValueError(f"ffn_act {self.ffn_act!r}")
        for field in ("layer_pattern", "mtp_pattern"):
            bad = sorted(set(getattr(self, field)) - set(LAYER_PATTERN))
            if bad:
                raise ValueError(
                    f"{field} holds {bad}: a layer is one of "
                    f"{sorted(LAYER_PATTERN)}")
        if self.layer_pattern and (
                len(self.layer_pattern) < self.num_layers or self.layer_types
                or self.hc_mult > 1 or self.leading_dense
                or bool(self.num_nextn) != bool(self.mtp_pattern)):
            raise ValueError(
                "layer_pattern names every layer's one sub-block, "
                f"num_layers ({self.num_layers}) of them or more, in place "
                "of layer_types and leading_dense, on the plain residual; "
                "a predicted-ahead module behind it (num_nextn) is spelled "
                "the same way, by mtp_pattern, and not without one")
        if self.mtp_pattern and not self.layer_pattern:
            raise ValueError(
                "mtp_pattern spells the predicted-ahead module of a "
                f"layer_pattern model: {self.mtp_pattern!r}")
        if self.mb_per_layer and (
                self.mb_per_layer != 2 or self.num_layers % 4
                or self.layer_types or self.layer_pattern or self.gqa_layers
                or self.linear_attn_config or self.kv_lora_rank
                or self.sa_config or self.num_heads % 2
                or self.num_kv_heads % 2 or self.sliding_window < 1):
            raise NotImplementedError(
                "mb_per_layer: a SambaY decoder alternates recurrent and "
                "attention layers (mb_per_layer 2) over a depth that is a "
                "multiple of 4, names no layer's mixer any other way, pairs "
                "its q and its KV heads and states its sliding_window")
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(f"norm_type {self.norm_type!r}")
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown:
            raise ValueError(
                f"layer_types {sorted(unknown)}: not in {sorted(MIXERS)}")
        if self.layer_types and len(self.layer_types) < self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_layers is {self.num_layers}")
        if (self.norm_type == "layernorm" or self.attn_bias) and (
                not {m for m, _ in self.layer_kinds} <= SAMBAY_MIXERS
                or self.num_experts or self.hc_mult > 1 or self.num_nextn
                or self.block_norm != "input"):
            raise NotImplementedError(
                "LayerNorm with a bias as the blocks' norm and biases on "
                "the attention projections are implemented for the mixers "
                f"of a SambaY decoder ({sorted(SAMBAY_MIXERS)}) and the "
                "dense FFN, on one stream, the norm on a block's input")
        if (self.hc_mult > 1 or self.num_nextn) and any(
                MIXERS[m].publishes or MIXERS[m].reads or MIXERS[m].indexed
                for m, _ in self.layer_kinds):
            raise NotImplementedError(
                "a mixer that publishes, reads or takes its layer's index "
                "on several residual streams or before a predicted-ahead "
                "module")
        _published(self.layer_runs)     # a reader has a publisher before it
        if self.gqa_layers and (
                self.layer_types or self.layer_pattern or self.kv_lora_rank
                or set(self.linear_group) & {"kda_layers", "full_attn_layers"}
                or self.linear_group.get("num_kv_heads") not in (
                    None, self.kda_heads)):
            raise ValueError(
                "gqa_layers names the softmax layers, counted from 0, of a "
                "model whose other layers are 'kda' at linear_attn_config's "
                "sizes (as many value heads as key heads), in place of "
                "layer_types, layer_pattern, latent attention and "
                f"linear_attn_config's own lists: {self.gqa_layers}")
        if (self.linear_attn_config and not self.layer_types
                and not self.gqa_layers):
            group = self.linear_group
            named = sorted(group.get("kda_layers", ())
                           + group.get("full_attn_layers", ()))
            if (self.layer_pattern or not self.kv_lora_rank
                    or named[:self.num_layers] != list(
                        range(1, self.num_layers + 1))):
                raise ValueError(
                    "linear_attn_config names every layer's mixer once, "
                    "counted from 1, 'kda' or latent attention "
                    "(kv_lora_rank), in place of layer_types and "
                    f"layer_pattern: {group}")
        if "kda" in {m for m, _ in self.layer_kinds} and not self.kda_heads:
            raise ValueError(
                "a 'kda' layer takes its heads from linear_attn_config "
                "(num_heads, head_dim, short_conv_kernel_size)")
        if self.position_embedding == ROPE_BY_KIND:
            groups = dict(self.rope_parameters or ())
            for mixer in {m for m, _ in self.layer_kinds} & set(ROTARY_MIXERS):
                kind = ROPE_KINDS[mixer == "sliding_attention"]
                if "rope_theta" not in dict(groups.get(kind, ())):
                    raise ValueError(
                        f"position_embedding {ROPE_BY_KIND!r}: "
                        "rope_parameters holds no rope_theta for the "
                        f"model's {kind} layers")
        if self.heads_per_layer:
            self._check_heads_per_layer()
        for windowed in (False, True):
            self.rotary_dim(windowed)   # refuses a share it cannot rotate
            kind = rope_type(self.rope_rule(windowed)[1])
            if kind not in ("default", "yarn"):
                raise NotImplementedError(
                    f"rope scaling of type {kind!r}: the plain tables "
                    "('default') and YaRN's are implemented, and a model "
                    "is never trained with plain frequencies in place of "
                    "the ones its file states")
        if self.looped:
            self._check_looped()

    def _check_heads_per_layer(self):
        """One count of query heads a KIND of layer, each whole groups of
        the KV heads, on the softmax mixers that read it."""
        mixers = [m for m, _ in self.layer_kinds]
        if len(self.heads_per_layer) < self.num_layers:
            raise ValueError(
                f"heads_per_layer names {len(self.heads_per_layer)} layers, "
                f"num_layers is {self.num_layers}")
        other = sorted(set(mixers) - {"attention", "full_attention",
                                      "sliding_attention"})
        if other or self.hc_mult > 1 or self.num_nextn:
            raise NotImplementedError(
                "heads_per_layer is read by the softmax mixers (attention, "
                "full_attention, sliding_attention) of a model on one "
                f"residual stream without a predicted-ahead module: {other}")
        for windowed in (False, True):
            counts = sorted({
                h for h, m in zip(self.heads_per_layer, mixers)
                if (m == "sliding_attention") == windowed})
            if len(counts) > 1:
                raise ValueError(
                    f"heads_per_layer gives the {ROPE_KINDS[windowed]} "
                    f"layers {counts} query heads: ONE count a kind of "
                    "layer (a run of layers is one stack of tensors)")
            if counts and (counts[0] < 1 or counts[0] % self.num_kv_heads):
                raise ValueError(
                    f"heads_per_layer: {counts[0]} query heads are not "
                    f"whole groups of the {self.num_kv_heads} KV heads")

    def _check_looped(self):
        """What is built of a looped model, and a refusal by message of
        what is not: never a silent single pass."""
        group = self.loop_group
        if set(group) != {"passes", "entropy_coef"} or not (
                isinstance(group["passes"], int) and group["passes"] >= 1
                and group["entropy_coef"] >= 0.0):
            raise ValueError(
                "looped: {passes >= 1 (a whole number), entropy_coef >= 0}: "
                f"{group}")
        if self.attn_impl in ("ring", "ulysses"):
            raise NotImplementedError(
                f"looped with attn_impl {self.attn_impl!r}: the passes are "
                "not built over a sequence split over 'sp'")
        if (self.num_nextn or self.block_diffusion or self.hc_mult > 1
                or self.select_bias and self.num_experts
                or any(MIXERS[m].publishes or MIXERS[m].reads
                       for m, _ in self.layer_kinds)):
            raise NotImplementedError(
                "looped is built for a stack on one residual stream under "
                "the next-token loss of every pass: not with a "
                "predicted-ahead module (num_nextn), block_diffusion, "
                "several residual streams (hc_mult), a router's selection "
                "bias (its counts are a step's, not a pass's) or a mixer "
                "that publishes or reads across layers — what one pass "
                "published the next would have to read")

    def _check_block_diffusion(self):
        """What is built of the denoising objective, and a refusal by
        message of what is not: never a silent next-token run."""
        group = self.bd_group
        missing = {"block_length", "mask_token_id", "eps",
                   "noise_seed"} - set(group)
        if missing or group["block_length"] < 1 or not (
                0 <= group["mask_token_id"] < self.vocab_size
                and 0.0 < group["eps"] < 1.0):
            raise ValueError(
                "block_diffusion: {block_length >= 1, mask_token_id below "
                "vocab_size, eps in (0, 1), noise_seed}: "
                f"{group}")
        if self.attn_impl in ("ring", "ulysses"):
            raise NotImplementedError(
                f"block_diffusion with attn_impl {self.attn_impl!r}: the "
                "block rule is built into the flash kernels and the "
                "reference, not into a sequence split over 'sp'")
        if (self.num_nextn or self.sa_config or self.sliding_window
                or self.kv_lora_rank or self.hc_mult > 1
                or self.layer_pattern or self.mb_per_layer
                or self.gqa_layers or self.linear_attn_config
                or set(self.layer_types) - {"attention", "full_attention",
                                            "block_attention"}):
            raise NotImplementedError(
                "block_diffusion is built for a model whose every mixer is "
                "plain softmax attention on one residual stream: not with a "
                "predicted-ahead module (num_nextn), an indexer (sa_config), "
                "a sliding window, latent attention or any other mixer — "
                "none of them knows the two streams")

    @property
    def bd_group(self) -> Dict[str, Any]:
        return dict(self.block_diffusion or ())

    @property
    def loop_group(self) -> Dict[str, Any]:
        return dict(self.looped or ())

    @property
    def passes(self) -> int:
        """The times the stack is run: a looped model's T, 1 for any other."""
        return self.loop_group.get("passes", 1)

    @property
    def bd_block(self) -> int:
        """The block length of a block-diffusion model, 0 for any other."""
        return self.bd_group.get("block_length", 0)

    @property
    def qkv_dim(self) -> int:
        return self.num_heads * self.head_dim

    def q_heads(self, windowed: bool) -> int:
        """The query heads of a softmax layer of this kind (``windowed``: a
        "sliding_attention" layer): the count ``heads_per_layer`` gives the
        kind's layers, ``num_heads`` in a model without the list (and for a
        kind the model has no layer of)."""
        mixers = (m for m, _ in self.layer_kinds)
        return next((h for h, m in zip(self.heads_per_layer, mixers)
                     if (m == "sliding_attention") == windowed),
                    self.num_heads)

    def rotary_dim(self, windowed: bool) -> int:
        """The dimensions of a head, from its first on, that a softmax
        layer of this kind rotates: ``head_dim`` times the
        ``partial_rotary_factor`` of the kind's rotary group, 1 where it
        states none (latent attention states its rotary part by
        ``qk_rope_dim`` and takes no share)."""
        share = dict(self.rope_rule(windowed)[1]).get(
            "partial_rotary_factor", 1.0)
        width = self.head_dim * share
        if (not 0.0 < share <= 1.0 or width != int(width) or int(width) % 2
                or share != 1.0 and self.kv_lora_rank):
            raise ValueError(
                f"partial_rotary_factor {share} of a head of "
                f"{self.head_dim}: the rotary width {width} is no multiple "
                "of 2 within a softmax mixer's head")
        return int(width)

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def s6_inner(self) -> int:
        return self.s6_expand * self.embed_dim

    @property
    def s6_rank(self) -> int:
        """The width dt comes up from."""
        if self.s6_dt_rank == "auto":
            return -(-self.embed_dim // 16)
        return self.s6_dt_rank

    @property
    def gdn_key_inner(self) -> int:
        return self.gdn_heads * self.gdn_key_dim

    @property
    def gdn_value_inner(self) -> int:
        return self.gdn_heads * self.gdn_value_dim

    @property
    def gdn_conv_dim(self) -> int:
        """What the convolution runs over: q, k and v side by side."""
        return 2 * self.gdn_key_inner + self.gdn_value_inner

    @property
    def linear_group(self) -> Dict[str, Any]:
        """``linear_attn_config`` as a dict ({} of a model without one)."""
        return dict(self.linear_attn_config or ())

    @property
    def index_group(self) -> Dict[str, Any]:
        """``sa_config`` as a dict ({} of a model without one)."""
        return dict(self.sa_config or ())

    @property
    def index_heads(self) -> int:
        return self.index_group.get("indexer_num_heads", 0)

    @property
    def index_dim(self) -> int:
        return self.index_group.get("indexer_head_dim", 0)

    @property
    def index_topk(self) -> int:
        """The keys a query reads (0: a model without an indexer)."""
        return self.index_group.get("topk", 0)

    @property
    def kda_heads(self) -> int:
        return self.linear_group.get("num_heads", 0)

    @property
    def kda_head_dim(self) -> int:
        """A head's keys and values alike."""
        return self.linear_group.get("head_dim", 128)

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_rank(self) -> int:
        """The width the decay and the output gate come up from: a head's."""
        return self.kda_head_dim

    @property
    def kda_conv(self) -> int:
        return self.linear_group.get("short_conv_kernel_size", 4)

    @property
    def latent_qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def dense_width(self) -> int:
        return self.dense_mlp_dim or self.mlp_dim

    @property
    def local_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def shared_width(self) -> int:
        return self.shared_mlp_dim or self.shared_experts * self.mlp_dim

    @property
    def residual_init_scale(self) -> float:
        if not self.rescale_prenorm_residual:
            return 1.0
        return (2 * (self.published_layers or self.num_layers)) ** -0.5

    @property
    def select_bias(self) -> bool:
        return self.topk_method == "noaux_tc"

    def rotary(self, windowed: bool) -> bool:
        """Whether a softmax mixer rotates its q and k: everywhere under
        ``rope``, nowhere under ``nope``, and under ``rope_windowed`` in
        the layers with a window alone — the ONE place that says which
        kind of layer carries the position signal."""
        if self.position_embedding == "rope_windowed":
            return windowed
        return self.position_embedding in ("rope", ROPE_BY_KIND)

    def rope_rule(self, windowed: bool) -> Tuple[float, Tuple]:
        """``(theta, the scaling group's items)`` of the tables a layer of
        this kind rotates by (``ops.layers.scaled_rope`` takes the two): the
        model's one ``rope_theta`` and ``rope_scaling``, or under
        ``rope_by_layer_type`` the group ``rope_parameters`` holds for the
        kind — ``sliding_attention`` for a layer with a window,
        ``full_attention`` for every other."""
        if self.position_embedding != ROPE_BY_KIND:
            return self.rope_theta, self.rope_scaling or ()
        group = dict(dict(self.rope_parameters or ()).get(
            ROPE_KINDS[windowed], ()))
        return group.pop("rope_theta", self.rope_theta), tuple(
            sorted(group.items()))

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, FFN) of every layer: the mixer ``layer_types`` names
        (latent attention for a model with a ``kv_lora_rank``, else
        attention; ``indexed`` for attention in a model with an
        ``sa_config``), ``linear_attn_config`` lists or ``gqa_layers``
        leaves to ``kda``, a dense FFN in the ``leading_dense`` first layers
        (or where ``mlp_layer_types`` says ``dense``)
        and in a model without experts, the expert layer elsewhere; or, of a
        model with a ``layer_pattern``, the pair each character stands
        for (``LAYER_PATTERN``)."""
        if self.layer_pattern:
            return tuple(LAYER_PATTERN[c]
                         for c in self.layer_pattern[:self.num_layers])
        if self.mb_per_layer:
            return tuple((m, "dense") for m in sambay_mixers(self.num_layers))
        mixers = self.layer_types[:self.num_layers] or (
            ("latent" if self.kv_lora_rank else "attention",)
            * self.num_layers)
        if self.gqa_layers:
            mixers = tuple("attention" if i in self.gqa_layers else "kda"
                           for i in range(self.num_layers))
        elif self.linear_attn_config and not self.layer_types:
            kda = self.linear_group.get("kda_layers", ())
            mixers = tuple("kda" if i + 1 in kda else "latent"
                           for i in range(self.num_layers))
        if self.sa_config:
            mixers = tuple("indexed" if m in ("attention", "full_attention")
                           else m for m in mixers)
        if self.block_diffusion:
            mixers = ("block_attention",) * self.num_layers
        if self.mlp_layer_types:
            return tuple((mixer, "moe" if ffn == "sparse" else "dense")
                         for mixer, ffn in zip(mixers, self.mlp_layer_types))
        return tuple(
            (mixer, "moe" if self.num_experts and i >= self.leading_dense
             else "dense") for i, mixer in enumerate(mixers))

    @property
    def kind_runs(self) -> Tuple[Tuple[Tuple[str, str], int], ...]:
        """The model as maximal runs of one kind of layer:
        (((mixer, FFN), layers), ...)."""
        return _as_runs(self.layer_kinds)

    @property
    def layer_runs(self) -> Tuple[Tuple[str, int], ...]:
        """``kind_runs`` by the mixer alone: ((mixer, layers), ...)."""
        return tuple((mixer, n) for (mixer, _), n in self.kind_runs)

    @property
    def mtp_runs(self):
        """The predicted-ahead module's block: the layers ``mtp_pattern``
        spells, as maximal runs of one kind; without one, one expert layer
        (a dense one in a model without experts) of the model's last
        mixer."""
        if self.mtp_pattern:
            return _as_runs(LAYER_PATTERN[c] for c in self.mtp_pattern)
        mixer = self.layer_kinds[-1][0]
        return (((mixer, "moe" if self.num_experts else "dense"), 1),)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """CI-sized config: runs on one CPU device in seconds."""
        defaults = dict(vocab_size=256, embed_dim=64, num_layers=2,
                        num_heads=4, num_kv_heads=4, head_dim=16, mlp_dim=128,
                        max_seq_len=64, dtype=jnp.float32, remat=False,
                        attn_impl="reference")
        defaults.update(kw)
        return LlamaConfig(**defaults)


def sambay_mixers(depth: int) -> Tuple[str, ...]:
    """The mixers of a SambaY decoder of ``depth`` layers (a multiple of
    4), as ``LlamaConfig.mb_per_layer`` describes them: at 32, M W x 8, M F,
    then G C x 7."""
    half = depth // 2
    return tuple(
        ("mamba1" if i <= half else "gmu") if i % 2 == 0
        else "diff_sliding" if i < half
        else "diff_full" if i == half + 1 else "diff_cross"
        for i in range(depth))


# Those mixers (depth 8 holds every kind): the ones that take LayerNorms
# and projection biases.
SAMBAY_MIXERS = frozenset(sambay_mixers(8))


def _as_runs(kinds) -> Tuple[Tuple[Tuple[str, str], int], ...]:
    """Layers' kinds in order as maximal runs: (((mixer, FFN), layers),
    ...)."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return tuple((kind, n) for kind, n in runs)


def _layer_shapes(cfg: LlamaConfig, kind=("attention", "dense")
                  ) -> Dict[str, Param]:
    """name -> ``Param`` of a layer of this kind (mixer, FFN): the mixer's
    tensors, the FFN's, the residual's maps."""
    mixer, ffn = kind
    return {**MIXERS[mixer].shapes(cfg), **FFNS[ffn].shapes(cfg),
            **residual.shapes(cfg)}


def _per_run(runs: list):
    """``params["layers"]`` (or a tree shaped like it) from one entry a
    run: the entry itself for a model of one kind of layer."""
    return runs[0] if len(runs) == 1 else tuple(runs)


def _runs(layers, runs) -> list:
    """[(kind, that run's entry of ``layers``), ...] for ``runs`` as
    ``LlamaConfig.kind_runs`` gives them: ``_per_run``'s inverse."""
    if len(runs) == 1:
        layers = (layers,)
    return [(kind, lp) for (kind, _), lp in zip(runs, layers)]


def _mtp_shapes(cfg: LlamaConfig):
    """What a predicted-ahead module holds beside its block (arXiv:
    2412.19437 §2.2): a norm each for the stream and the next token's
    embedding, the projection of the two side by side, its own last norm.
    The embedding table and the head are the model's."""
    d = cfg.embed_dim
    norm = Param((d,), ("embed",), ones)
    return {"h_norm": norm, "e_norm": norm,
            "proj": Param((2 * d, d), (None, "kernel_in")),
            "final_norm": norm}


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    def stacks(runs):
        return _per_run([
            {k: p.axes for k, p in _layer_shapes(cfg, kind).items()}
            for kind, _ in runs])

    axes = {
        "embed": ("vocab", "kernel_in"),
        "layers": stacks(cfg.kind_runs),
        "final_norm": ("embed",),
    }
    if cfg.norm_type == "layernorm":
        axes["final_norm_bias"] = ("embed",)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("kernel_in", "vocab")
    if cfg.looped:
        axes["exit_gate"], axes["exit_gate_bias"] = ("embed",), ()
    if cfg.num_nextn:
        axes["mtp"] = {**{k: p.axes for k, p in _mtp_shapes(cfg).items()},
                       "layers": stacks(cfg.mtp_runs)}
    return axes


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Every tensor by the initialiser its ``Param`` names (scaled normal,
    fan-in, where it names none), in ``cfg.param_dtype`` where it names no
    dtype.  A tied table is initialised as the head it also is (fan-in: the
    step-0 loss is then ln(vocab) to a hundredth)."""
    def run_shapes(runs):
        return [(n, _layer_shapes(cfg, kind)) for kind, n in runs]

    main = run_shapes(cfg.kind_runs)
    mtp = run_shapes(cfg.mtp_runs) if cfg.num_nextn else []
    n_tensors = sum(len(shapes) for _, shapes in main + mtp) + 3 + (
        len(_mtp_shapes(cfg)) if mtp else 0) + (
            cfg.norm_type == "layernorm") + bool(cfg.looped)
    keys = iter(jax.random.split(key, n_tensors))

    def drawn(p: Param, *stacked):
        # ``ones`` alone takes no key: a norm's weight never drew one
        key = None if p.init is ones else next(keys)
        return p.init(key, (*stacked, *p.shape)).astype(
            p.dtype or cfg.param_dtype)

    def stack(n, shapes):
        return {name: drawn(p, n) for name, p in shapes.items()}

    def matrix(shape, fan_in):
        return normal(next(keys), shape, fan_in).astype(cfg.param_dtype)

    layers = _per_run([stack(n, shapes) for n, shapes in main])
    params = {
        "embed": matrix((cfg.vocab_size, cfg.embed_dim),
                        cfg.embed_dim if cfg.tie_embeddings
                        else (cfg.embed_init_std or 1.0) ** -2),
        "layers": layers,
        "final_norm": jnp.ones((cfg.embed_dim,), cfg.param_dtype),
    }
    if cfg.norm_type == "layernorm":
        params["final_norm_bias"] = drawn(
            Param((cfg.embed_dim,), ("embed",), small(BIAS_STD)))
    if not cfg.tie_embeddings:
        params["lm_head"] = matrix((cfg.embed_dim, cfg.vocab_size),
                                   cfg.embed_dim)
    if mtp:
        params["mtp"] = {name: drawn(p)
                         for name, p in _mtp_shapes(cfg).items()}
        params["mtp"]["layers"] = _per_run(
            [stack(n, shapes) for n, shapes in mtp])
    if cfg.looped:
        # the exit gate: a projection to ONE number a token, its bias at 0
        params["exit_gate"] = matrix((cfg.embed_dim,), cfg.embed_dim)
        params["exit_gate_bias"] = jnp.zeros((), cfg.param_dtype)
    return params


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (batch, seq) int32 -> (logits f32 (b, s, vocab), aux_loss).

    Global-view path: call under jit with a mesh context; sharding
    constraints steer XLA's partitioner.  (The pipeline-parallel path is
    ``parallel.pipeline.forward_pipelined`` — manual SPMD.)
    """
    if cfg.block_diffusion:
        # the denoising pass with step 0's noise: the noised stream's logits
        # (b, s, vocab), the ones ``loss_fn`` scores
        (logits, _, _), aux, _ = _denoising_pass(params, tokens, cfg, mesh,
                                                 rules, 0)
        return logits, _mean_aux(aux, cfg, cfg.kind_runs)
    if cfg.looped:
        # no exit is taken early: the last pass's logits (its stream is
        # normed already)
        h, aux, _ = _looped(params, tokens, None, cfg, mesh, rules)
        with jax.named_scope("lm_head"):
            logits = _head_product(params, h, cfg, _make_cst(mesh, rules))
        return logits, _mean_aux(aux, cfg, cfg.kind_runs * cfg.passes)
    h, aux, _ = _hidden(params, tokens, cfg, mesh, rules)
    return (_lm_head(params, h, cfg, _make_cst(mesh, rules)),
            _mean_aux(aux, cfg, cfg.kind_runs))


BD_MASKED_SHARE = "bd_masked_share"


def _denoising_streams(tokens, cfg: LlamaConfig, step):
    """The block-diffusion corruption of ``tokens (b, L)`` (scope
    ``bd_noise``): ``([xt ; x0] (b, 2 L), m (b, L), the loss weights m / p
    (b, L) float32)``.  The key is ``fold_in(PRNGKey(noise_seed), step)``:
    a step's draws follow from its number, so every step draws fresh noise
    and a resumed job repeats its own.  Split in two: one ``t ~ U(0, 1)`` a
    sequence, ``p = (1 - eps) t + eps``; one uniform a position, masked
    where it lies under ``p``.  WHICH positions are masked is ``m``, never
    ``xt == mask`` (a data token may equal the mask id)."""
    group = cfg.bd_group
    with jax.named_scope("bd_noise"):
        key_t, key_m = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(group["noise_seed"]), step))
        t = jax.random.uniform(key_t, (tokens.shape[0], 1), jnp.float32)
        p = (1.0 - group["eps"]) * t + group["eps"]
        m = jax.random.uniform(key_m, tokens.shape, jnp.float32) < p
        noised = jnp.where(m, jnp.asarray(group["mask_token_id"],
                                          tokens.dtype), tokens)
        return (jnp.concatenate([noised, tokens], axis=1), m,
                m.astype(jnp.float32) / p)


def _denoising_pass(params, tokens, cfg: LlamaConfig, mesh, rules, step):
    """One pass over ``[xt ; x0]`` of ``tokens (b, L)``: ``((the noised
    half's logits (b, L, vocab), m, the loss weights), aux, the layers'
    counts)``.  The layers see ``b`` sequences of 2 L rows (the mixer
    ``block_attention`` knows they are two streams); the head runs on the
    noised half's L rows."""
    streams, m, weights = _denoising_streams(tokens, cfg, step)
    h, aux, counts = _hidden(params, streams, cfg, mesh, rules)
    logits = _lm_head(params, h[:, :tokens.shape[1]], cfg,
                      _make_cst(mesh, rules))
    return (logits, m, weights), aux, counts


def _embed(params, tokens, cfg: LlamaConfig, mesh, rules):
    """The tokens' embeddings (inside the scope ``embed``): the rows of the
    table that the tokens name.  Where the rules in force lay the vocabulary
    over mesh axes of more than one device, each shard takes the rows it
    holds and leaves zeros for the other shards' tokens, and the parts are
    summed over those axes; the table is never gathered over them, and its
    gradient is the scatter-add of each token's cotangent into the shard
    that holds its row."""
    axes = logical_to_mesh_axes(("vocab",), rules)[0] or ()
    axes = tuple(a for a in ((axes,) if isinstance(axes, str) else axes)
                 if mesh is not None and mesh.shape[a] > 1)

    def take(table, tokens, **kw):
        return jnp.take(table, tokens, axis=0, **kw).astype(cfg.dtype)

    def take_held(shard, tokens):
        n = shard.shape[0]
        local = tokens - n * jax.lax.axis_index(axes)
        held = (local >= 0) & (local < n)
        return jnp.where(held[..., None], take(shard, local, mode="clip"),
                         0)[None]

    if axes:
        parts = manual_shard_map(take_held, axes, in_specs=(P(axes), P()),
                                 out_specs=P(axes), mesh=mesh)
        x = parts(params["embed"], tokens).sum(0)
    elif mesh is None:
        x = take(params["embed"], tokens)
    else:
        # Left to the partitioner, the rows are taken sequence-first.  The
        # gradient of a table read twice (a predicted-ahead module's
        # tokens beside the model's) is ONE scatter-add: XLA joins the two
        # before it partitions them, by concatenating indices and
        # cotangents along their leading dimension — the sequence, which
        # no chip shares, where the batch's rows would then be moved
        # between the chips that share them.
        x = take(params["embed"], tokens.swapaxes(0, 1)).swapaxes(0, 1)
    x = scaled(x, cfg.embedding_multiplier)
    return _make_cst(mesh, rules)(x, ("batch", "seq", "embed"))


def _hidden(params, tokens, cfg: LlamaConfig, mesh, rules):
    """Embedding and layers: ``(h (b, s, d) before the last norm, aux,
    each run's per-layer expert counts or None)``."""
    with jax.named_scope("embed"):
        x = to_streams(_embed(params, tokens, cfg, mesh, rules), cfg)
    x, aux, counts = _scan_layers(params["layers"], x, cfg, mesh, rules)
    return from_streams(x, cfg), aux, counts


def _scan_layers(layers, x, cfg: LlamaConfig, mesh, rules,
                 sp_manual: bool = False, aux=None, runs=None):
    """The layers over ``x`` (the streams side by side where the model has
    several): one ``lax.scan`` a run of one kind of layer (``runs``:
    ``cfg.kind_runs``), over that run's stacked parameters, each under the
    layer checkpoint.  Returns ``(x, aux, counts)``: ``counts`` holds, a
    run, what its layers hand out of the scan — the experts' assignments
    ``(layers, E)`` of a run whose router has a selection bias, else None.
    What a mixer PUBLISHES for later layers (``Block.publishes``) leaves its
    run's scan beside that and enters the scans of the runs that READ it
    (``_published`` says which run hands on what)."""
    carry = (x, _zero_aux(cfg) if aux is None else aux)
    runs = cfg.kind_runs if runs is None else runs
    wanted = _published(tuple((mixer, n) for (mixer, _), n in runs))
    counts, shared, first = [], {}, 0
    for (kind, stacked), (_, n), publish in zip(_runs(layers, runs), runs,
                                                wanted):
        scan = functools.partial(_scan_run, cfg, mesh, rules, sp_manual,
                                 kind, shared)
        start = first
        if publish and n > 1:
            # the run's LAST layer is the one later layers read: the others
            # go first, in a scan that hands nothing out
            carry, _ = scan(carry, jax.tree.map(lambda a: a[:-1], stacked),
                            start)
            stacked, start = (jax.tree.map(lambda a: a[-1:], stacked),
                              first + n - 1)
        carry, out = scan(carry, stacked, start, publish)
        if publish:
            out, made = out
            shared.update(jax.tree.map(lambda a: a[0], made))
        counts.append(out)
        first += n
    return (*carry, counts)


def _scan_run(cfg: LlamaConfig, mesh, rules, sp_manual, kind, shared, carry,
              stacked, first: int, publish=()):
    """One ``lax.scan`` over the ``stacked`` layers of ``kind``, the first
    of them layer ``first`` of the model.  A mixer that reads takes its
    arrays out of ``shared`` — constants of this scan, held ONCE however
    many layers read them, their gradient summed over the readers —; one
    that is ``indexed`` its layer's number beside its tensors; ``publish``:
    the names the scan hands out beside the layers' own output (stacked
    over the layers as every output of a scan: the caller scans ONE
    publishing layer)."""
    mixer = MIXERS[kind[0]]
    if mixer.indexed:
        layers = jax.tree.leaves(stacked)[0].shape[0]
        stacked = dict(stacked, layer_index=first + jnp.arange(
            layers, dtype=jnp.float32))
    layer_fn = _make_layer_fn(
        cfg, mesh, rules, sp_manual, kind, publish=publish,
        shared={name: shared[name] for name in mixer.reads})
    if cfg.remat:
        layer_fn = _checkpoint(layer_fn)
    return jax.lax.scan(layer_fn, carry, stacked)


def _published(layer_runs) -> Tuple[Tuple[str, ...], ...]:
    """For each run of ``layer_runs`` (``((mixer, layers), ...)``) the names
    it has to hand on: those of its mixer's ``publishes`` that a LATER run
    reads before another run publishes them again (a reader takes the
    nearest earlier publication; what nobody reads is never handed out of
    a scan).  A reader without a publisher before it is refused."""
    out, held = [], set()
    for i, (mixer, _) in enumerate(layer_runs):
        missing = set(MIXERS[mixer].reads) - held
        if missing:
            raise ValueError(
                f"layer run {i} ({mixer!r}) reads {sorted(missing)}, which "
                "no earlier layer publishes")
        held |= set(MIXERS[mixer].publishes)
        names = []
        for name in MIXERS[mixer].publishes:
            later = next((m for m, _ in layer_runs[i + 1:]
                          if name in MIXERS[m].publishes + MIXERS[m].reads),
                         None)
            if later is not None and name in MIXERS[later].reads:
                names.append(name)
        out.append(tuple(names))
    return tuple(out)


def _checkpoint(layer_fn):
    """The layer checkpoint of every path (``cfg.remat``): the backward
    pass recomputes the layer from its input, except the few residuals
    that are dear to recompute and cheap to hold, named where they are
    made: what the registered blocks declare (``Block.saved``).  A layer
    that never produces a name (reference attention, a dense FFN) saves
    nothing under it."""
    return jax.checkpoint(
        layer_fn, policy=jax.checkpoint_policies.save_only_these_names(
            *_saved_names()))


def _saved_names() -> Tuple[str, ...]:
    return tuple(dict.fromkeys(
        n for b in (*MIXERS.values(), *FFNS.values()) for n in b.saved))


def _all_runs(cfg: LlamaConfig):
    """The model's runs and, where it has one, its predicted-ahead
    module's."""
    return cfg.kind_runs + (cfg.mtp_runs if cfg.num_nextn else ())


def _layer_stats(cfg: LlamaConfig, kind) -> Dict[str, str]:
    """The statistics a layer of ``kind`` folds into what the scan
    carries, each with how layers combine it (``Block.stats``)."""
    return {**MIXERS[kind[0]].stats(cfg), **FFNS[kind[1]].stats(cfg)}


def _zero_aux(cfg: LlamaConfig):
    """What the layer scan carries beside the activations: the auxiliary
    loss and every statistic a block of this model reports, float32
    scalars by name — or, where no block reports any (a dense model), the
    auxiliary loss alone, a bare 0."""
    zero = jnp.zeros((), jnp.float32)
    names = ["aux_loss"] + [k for kind, _ in _all_runs(cfg)
                            for k in _layer_stats(cfg, kind)]
    return dict.fromkeys(names, zero) if len(set(names)) > 1 else zero


def _mean_aux(aux, cfg: LlamaConfig, runs):
    """What the scan carried over ``runs``: a ``mean`` divided by the
    layers that added to it, a ``sum`` and a ``max`` as they are."""
    if not isinstance(aux, dict):
        return aux / (cfg.num_layers * cfg.passes)
    how, layers = {"aux_loss": "mean"}, dict.fromkeys(aux, 0)
    for kind, n in runs:
        for k, h in _layer_stats(cfg, kind).items():
            how[k], layers[k] = h, layers[k] + n
    return {k: v / max(layers[k], 1) if how[k] == "mean" else v
            for k, v in aux.items()}


def _lm_head(params, x, cfg: LlamaConfig, cst):
    """Final norm and head product -> f32 logits (scope ``lm_head``)."""
    with jax.named_scope("lm_head"):
        return _head_product(params, _final_norm(params, x, cfg), cfg, cst)


def _final_norm(params, x, cfg: LlamaConfig):
    return residual.norm(x, params["final_norm"],
                         params.get("final_norm_bias"), cfg.norm_eps)


def _head_product(params, x, cfg: LlamaConfig, cst):
    """The normed ``x`` through the head -> f32 logits (inside the scope
    ``lm_head``)."""
    if cfg.tie_embeddings:  # one table, read twice: its gradient is
        # the sum of both uses
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"].astype(cfg.dtype))
    else:
        logits = x @ params["lm_head"].astype(cfg.dtype)
    logits = scaled(logits.astype(jnp.float32), 1.0 / cfg.logits_scaling)
    return cst(logits, ("batch", "seq", "vocab"))


def _ut_pass(params, layers, x, aux, cfg: LlamaConfig, mesh, rules):
    """One pass of a looped model: the whole stack ``layers`` over ``x``
    (the layer checkpoint as on every path), then the model's ONE last norm
    -> ``(h, aux)``; ``h`` is what the exit gate and the head read AND what
    the next pass starts from."""
    x, aux, _ = _scan_layers(layers, x, cfg, mesh, rules, aux=aux)
    # keeps the pass's output, not the norm's float32 copies of it
    norm = _inputs_kept(lambda params, x: _final_norm(params, x, cfg), cfg)
    with jax.named_scope("lm_head"):
        return norm(params, x), aux


def _inputs_kept(fn, cfg: LlamaConfig):
    """``fn`` under a checkpoint (``cfg.remat``) that keeps what it reads of
    its inputs and nothing it makes.  For the body of the passes' scan,
    where nothing is merged across the checkpoint's edge anyway."""
    return jax.checkpoint(fn, prevent_cse=False) if cfg.remat else fn


def _exit_reading(params, h, targets, cfg: LlamaConfig, cst):
    """What the objective reads of a pass's ``h (b, s, d)``: the exit
    gate's logit and each position's next-token loss, ``(b, s)`` float32
    both.  The head and its loss run under a checkpoint of their own
    (``_inputs_kept``) that keeps ``h`` and nothing else: the backward pass
    makes the pass's float32 logits again, so ONE pass's logits and their
    gradient are alive at a time, forward and backward."""
    with jax.named_scope("ut_exit"):
        gate = jnp.einsum(
            "bsd,d->bs", h, params["exit_gate"].astype(cfg.dtype),
            preferred_element_type=jnp.float32) \
            + params["exit_gate_bias"].astype(jnp.float32)

    def head_nll(params, h):
        with jax.named_scope("lm_head"):
            logits = _head_product(params, h, cfg, cst)
        with jax.named_scope("loss"):
            return _row_nll(logits, targets)

    return gate, _inputs_kept(head_nll, cfg)(params, h)


def _looped(params, tokens, targets, cfg: LlamaConfig, mesh, rules):
    """A looped model's passes over ``tokens (b, s)``: ``(the last pass's
    h, aux, (gate logits, next-token losses) (T, b, s) or None without
    ``targets``)``.  ONE ``lax.scan`` over the passes whose constants are
    the parameters: the program holds one layer body a run and one
    norm-gate-head-loss body whatever T is, a shared tensor's gradient is
    the scan's sum over its T uses, and the backward pass of pass t reruns
    pass t's layers alone, from what the layer checkpoint kept of them."""
    cst = _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, mesh, rules)

    def one_pass(carry, _):
        h, aux = _ut_pass(params, params["layers"], *carry, cfg, mesh, rules)
        return (h, aux), None if targets is None else _exit_reading(
            params, h, targets, cfg, cst)

    (h, aux), read = jax.lax.scan(one_pass, (x, _zero_aux(cfg)), None,
                                  length=cfg.passes)
    return h, aux, read


def _exit_mixture(gates, nll, cfg: LlamaConfig):
    """The looped objective from every pass's gate logit and per-token loss
    ``(T, b, s)`` (scope ``ut_exit``): with ``lambda_t = sigmoid(g_t)`` a
    token exits after pass t < T with ``p_t = lambda_t prod_(j<t) (1 -
    lambda_j)`` and after the last with what is left (``g_T`` is not read),
    and ``loss = mean_i (sum_t p_t nll_t - beta H(p))``; nothing is
    detached.  Carried in logs (``log (1 - sigmoid(g)) = log_sigmoid(-g)``).
    Returns ``(loss, its step statistics)``."""
    with jax.named_scope("ut_exit"):
        passes = gates.shape[0]
        one = jnp.zeros_like(gates[:1])             # log 1
        survived = jnp.concatenate(                 # log S_(t-1), t = 1..T
            [one, jnp.cumsum(jax.nn.log_sigmoid(-gates[:-1]), axis=0)])
        log_p = survived + jnp.concatenate(
            [jax.nn.log_sigmoid(gates[:-1]), one])
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * nll, axis=0)
                        - cfg.loop_group["entropy_coef"] * entropy)
        steps = jnp.arange(1, passes + 1, dtype=jnp.float32)
        return loss, {
            **{f"ut_nll_{t + 1}": jnp.mean(nll[t]) for t in range(passes)},
            "ut_exit_entropy": jnp.mean(entropy),
            "ut_expected_steps": jnp.mean(jnp.einsum("t,t...->...", steps, p)),
            "ut_steps": jnp.float32(passes)}


def _make_cst(mesh, rules):
    if mesh is None:
        return lambda x, ax: x
    return lambda x, ax: with_logical_constraint(x, ax, mesh=mesh,
                                                 rules=rules)


def _make_layer_fn(cfg: LlamaConfig, mesh, rules, sp_manual: bool = False,
                   kind=("attention", "dense"), publish=(), shared=None):
    """One layer of ``kind`` (mixer, FFN) as a scan body over stacked
    layer params: a mixer (``blocks.MIXERS``) then an FFN
    (``blocks.FFNS``), each adding to the residual stream — or, in a model
    of several streams, each reading its input off them and written back
    into them through the layer's maps (``residual.hc_block``).  Shapes
    are read off the activation so the same body serves the full batch
    (forward) and microbatches (forward_pipelined).

    ``sp_manual``: the body runs inside a shard_map that is manual over
    'sp' (the pipeline path — jax/shardy cannot nest manual regions); what
    that means to a block is at ``blocks.base.Ctx``.

    ``shared``: what a mixer that READS takes, by name — arrays earlier
    layers published, constants of the scan this body runs in, so that a
    run of readers holds each ONCE and its gradient is summed over them.
    ``publish``: of what a mixer that PUBLISHES makes, the names a later
    layer reads: the body hands ``(out, {name: array})`` out of the scan.
    """
    ctx = Ctx(cfg, mesh, _make_cst(mesh, rules), sp_manual)
    mix, ffn = MIXERS[kind[0]].apply, FFNS[kind[1]].apply
    if shared:
        mix = functools.partial(mix, shared=shared)

    def layer_fn(carry, lp):
        x, aux = carry
        x, aux, *made = mix(ctx, x, aux, lp)
        x, aux, out = ffn(ctx, x, aux, lp)
        if publish:
            out = (out, {k: made[0][k] for k in publish})
        return (x, aux), out

    def streams_layer_fn(carry, lp):
        xs, aux = carry
        xs, aux = hc_block(ctx, xs, lp, "attn", lambda x: mix(
            ctx, x, aux, lp, residual=False))

        def ffn_block(x):
            y, aux_, out = ffn(ctx, x, aux, lp, residual=False)
            return y, (aux_, out)

        xs, (aux, out) = hc_block(ctx, xs, lp, "ffn", ffn_block)
        return (xs, aux), out

    return layer_fn if cfg.hc_mult == 1 else streams_layer_fn


def _one_kind(cfg: LlamaConfig, what: str) -> None:
    if cfg.looped:
        raise NotImplementedError(
            f"{what} runs a stage's layers ONCE a micro-batch; a looped "
            "model's passes over one stack, its exit gate and its objective "
            "are built on the normal path: make_train_step(pipelined=False)")
    if cfg.block_diffusion:
        raise NotImplementedError(
            f"{what} runs the next-token objective on one stream; a "
            "block_diffusion model's denoising pass over two streams is "
            "built on the normal path: make_train_step(pipelined=False)")
    if len(cfg.kind_runs) > 1 or cfg.hc_mult > 1 or cfg.num_nextn:
        raise NotImplementedError(
            f"{what} splits ONE stack of layers into stages; this model's "
            f"layers differ ({cfg.kind_runs}), or it carries several "
            "streams or a predicted-ahead module: train it with "
            "make_train_step(pipelined=False)")


def forward_pipelined(params: Dict[str, Any], tokens: jax.Array,
                      cfg: LlamaConfig, *, mesh: Mesh,
                      num_microbatches: int,
                      rules: Optional[LogicalAxisRules] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward: transformer layers split into ``pp``
    stages (parallel.pipeline), embed/head replicated across stages.

    Sequence parallelism composes: with attn_impl ring/ulysses the pipeline
    region is manual over {'pp','sp'} (jax/shardy cannot *nest* manual
    regions) — activations enter seq-sharded, RoPE offsets come from the
    'sp' rank, and attention runs inline over the bound axis.

    The expert layers' auxiliary losses and counters are not carried out of
    the pipeline stages (stage outputs must be activation-shaped): under pp
    an MoE model trains with both coefficients at 0.
    """
    from ray_tpu.parallel.pipeline import pipeline_apply, split_stages
    from ray_tpu.parallel.mesh import AXIS_PP

    _one_kind(cfg, "forward_pipelined")
    cst = _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, mesh, rules)

    sp_manual = cfg.attn_impl in ("ring", "ulysses") and \
        mesh.shape[AXIS_SP] > 1
    if sp_manual:
        # Inside the manual region 'seq' is device-local and 'sp' is bound:
        # strip it from the rules GSPMD sees.
        inner_rules = dict(rules if rules is not None else DEFAULT_RULES)
        inner_rules["seq"] = None
        x_spec = P(None, AXIS_SP, None)
        manual_axes = {AXIS_PP, AXIS_SP}
    else:
        inner_rules = rules
        x_spec = P()
        manual_axes = {AXIS_PP}
    def stage_fn(stage_params, x_mb):
        return _scan_layers(stage_params, x_mb, cfg, mesh, inner_rules,
                            sp_manual)[0]

    stages = split_stages(params["layers"], mesh.shape[AXIS_PP])
    x = pipeline_apply(stage_fn, stages, x, mesh=mesh,
                       num_microbatches=num_microbatches,
                       manual_axes=manual_axes, x_spec=x_spec)
    return _lm_head(params, x, cfg, cst), _zero_aux(cfg)


def _predicted_ahead(params, h, next_tokens, aux, cfg: LlamaConfig, mesh,
                     rules):
    """The predicted-ahead module (arXiv:2412.19437 §2.2) on the model's
    ``h (b, s, d)`` (the summed streams before the last norm) and the
    tokens that FOLLOW each position: the two are normed, laid side by
    side and projected back to d (scope ``mtp_in``), go through the
    module's own layers (``cfg.mtp_runs``: one more layer, or the ones its
    pattern spells) and its own last norm, and meet the model's
    head.  Position t then predicts token t + 2.  Returns ``(logits, aux,
    counts)`` as ``_hidden`` and the head do."""
    mp, cst = params["mtp"], _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        e = _embed(params, next_tokens, cfg, mesh, rules)
    with jax.named_scope("mtp_in"):
        x = jnp.concatenate([rms_norm(h, mp["h_norm"], cfg.norm_eps),
                             rms_norm(e, mp["e_norm"], cfg.norm_eps)], -1)
        x = cst(x @ mp["proj"].astype(cfg.dtype), ("batch", "seq", "embed"))
    with jax.named_scope("embed"):
        x = to_streams(x, cfg)
    x, aux, counts = _scan_layers(mp["layers"], x, cfg, mesh, rules,
                                  aux=aux, runs=cfg.mtp_runs)
    logits = _lm_head(dict(params, final_norm=mp["final_norm"]),
                      from_streams(x, cfg), cfg, cst)
    return logits, aux, counts


def update_router_bias(old, new, counts, cfg: LlamaConfig):
    """``new`` parameters with every selection bias moved from its ``old``
    value by the bias rule (``ops.moe.update_selection_bias``) in place
    of whatever the optimizer made of it; ``counts`` as
    ``loss_and_counts`` returns them."""
    def moved(old_stacks, new_stacks, runs, run_counts):
        return _per_run([
            new_lp if c is None else dict(
                new_lp, router_bias=update_selection_bias(
                    old_lp["router_bias"], c, cfg.bias_update_speed))
            for (_, old_lp), (_, new_lp), c in zip(
                _runs(old_stacks, runs), _runs(new_stacks, runs),
                run_counts)])

    out = dict(new, layers=moved(old["layers"], new["layers"],
                                 cfg.kind_runs, counts["layers"]))
    if cfg.num_nextn:
        out["mtp"] = dict(new["mtp"], layers=moved(
            old["mtp"]["layers"], new["mtp"]["layers"], cfg.mtp_runs,
            counts["mtp"]))
    return out


def pipeline_stage_params(params: Dict[str, Any],
                          num_stages: int) -> list:
    """Stage-sliced construction for the ACTOR pipeline
    (``train.pipeline_actors``): split the stacked layer params into
    ``num_stages`` contiguous slices, folding the embedding into stage
    0 and the final norm + LM head into the last stage — each stage
    actor then owns exactly its stage's tensors, nothing replicated."""
    layers = params["layers"]
    if not isinstance(layers, dict) or "lm_head" not in params:
        raise NotImplementedError(
            "the actor pipeline splits one stack of layers and gives the "
            "embedding and the head to different stages: not a model whose "
            "layers differ, nor one with a tied head")
    n_layers = next(iter(layers.values())).shape[0]
    if n_layers % num_stages:
        raise ValueError(
            f"{n_layers} layers not divisible by {num_stages} stages")
    per = n_layers // num_stages
    out = []
    for s in range(num_stages):
        sp: Dict[str, Any] = {
            "layers": {k: v[s * per:(s + 1) * per]
                       for k, v in layers.items()}}
        if s == 0:
            sp["embed"] = params["embed"]
        if s == num_stages - 1:
            sp["final_norm"] = params["final_norm"]
            sp["lm_head"] = params["lm_head"]
        out.append(sp)
    return out


def make_pipeline_stage_fn(cfg: LlamaConfig):
    """The uniform per-stage callable for ``train.pipeline_actors``:
    embeds on the stage holding ``embed`` (its input is then raw
    tokens), scans the stage's layer slice, and projects to logits on
    the stage holding ``lm_head``.  Key presence is trace-time static,
    so each stage jits to exactly its own program."""

    _one_kind(cfg, "make_pipeline_stage_fn")

    def stage_fn(sp, x):
        if "embed" in sp:
            with jax.named_scope("embed"):
                x = _embed(sp, x, cfg, None, None)
        x = _scan_layers(sp["layers"], x, cfg, None, None)[0]
        if "lm_head" in sp:
            x = _lm_head(sp, x, cfg, _make_cst(None, None))
        return x

    return stage_fn


def make_pipeline_loss_fn(cfg: LlamaConfig):
    """Next-token cross-entropy over the last stage's logits — the
    same mean-NLL ``loss_fn`` computes, as a ``(logits, targets)``
    pair for the actor pipeline's loss stage."""

    def pipeline_loss(logits, targets):
        with jax.named_scope("loss"):
            return _mean_nll(logits, targets)

    return pipeline_loss


def loss_and_counts(params: Dict[str, Any], batch: Dict[str, jax.Array],
                    cfg: LlamaConfig, *, mesh: Optional[Mesh] = None,
                    rules: Optional[LogicalAxisRules] = None,
                    forward_fn=None, step=0):
    """``loss_fn`` and, beside its metrics, what the train step needs and
    no metric can carry: ``(loss, (metrics, counts))``, ``counts`` the
    experts' assignments of every layer whose router has a selection bias
    (``{"layers": a run, "mtp": ...}``; ``update_router_bias`` reads it),
    None for a model without one.  ``step``: the step's number, which a
    block-diffusion model's noise is drawn from (nothing else reads it)."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    counts = ahead = weights = exits = None
    if cfg.looped:
        if forward_fn is not None:
            raise NotImplementedError(
                "looped with a replaced forward pass (the pipelined path): "
                "it would run ONE pass under the plain next-token loss")
        _, aux, read = _looped(params, inputs, targets, cfg, mesh, rules)
        aux = _mean_aux(aux, cfg, cfg.kind_runs * cfg.passes)
        loss, exits = _exit_mixture(*read, cfg)
    elif cfg.block_diffusion:
        if forward_fn is not None:
            raise NotImplementedError(
                "block_diffusion with a replaced forward pass (the pipelined "
                "path): it would run the next-token objective")
        # the denoising objective: position i's target is ITS OWN token
        targets = inputs
        (logits, masked, weights), aux, layer_counts = _denoising_pass(
            params, inputs, cfg, mesh, rules, step)
        if cfg.select_bias:
            counts = {"layers": layer_counts, "mtp": None}
        aux = dict(_mean_aux(aux, cfg, _all_runs(cfg)),
                   **{BD_MASKED_SHARE: jnp.mean(masked.astype(jnp.float32))})
    elif forward_fn is None:
        h, aux, layer_counts = _hidden(params, inputs, cfg, mesh, rules)
        logits = _lm_head(params, h, cfg, _make_cst(mesh, rules))
        if cfg.num_nextn:
            ahead, aux, mtp_counts = _predicted_ahead(
                params, h, targets, aux, cfg, mesh, rules)
        if cfg.select_bias:
            counts = {"layers": layer_counts,
                      "mtp": mtp_counts if cfg.num_nextn else None}
        aux = _mean_aux(aux, cfg, _all_runs(cfg))
    elif cfg.num_nextn or cfg.select_bias:
        raise NotImplementedError(
            "a predicted-ahead module and a selection bias need the layers' "
            "own outputs, which a replaced forward pass does not hand on")
    else:
        logits, aux = forward_fn(params, inputs)
    with jax.named_scope("loss"):
        if exits is None:
            loss = (_mean_nll(logits, targets) if weights is None
                    else _weighted_nll(logits, targets, weights))
        # the blocks' statistics are metrics under their own names; the
        # two that are losses also weigh in
        stats = aux if isinstance(aux, dict) else {"aux_loss": aux}
        total = loss + cfg.aux_loss_coef * stats["aux_loss"]
        if "z_loss" in stats:
            total = total + cfg.z_loss_coef * stats["z_loss"]
        if "idx_loss" in stats:     # the indexers' own, the layers' mean
            total = total + cfg.idx_loss_coef * stats["idx_loss"]
        metrics = {"loss": loss, **stats}
        if ahead is not None:
            # position t's target is token t + 2: the last has none
            seq = targets.shape[1]
            mtp_loss = _mean_nll(
                ahead, jnp.concatenate(
                    [targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1),
                (jnp.arange(seq) < seq - 1).astype(jnp.float32))
            total = total + cfg.mtp_loss_coef * mtp_loss
            metrics["mtp_loss"] = mtp_loss
        if exits is None:
            metrics["perplexity"] = jnp.exp(loss)
        else:   # ``loss`` is the looped objective, no log-likelihood: its
            # exponential would be no perplexity
            metrics.update(exits)
        return total, (metrics, counts)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: LlamaConfig, *, mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None,
            forward_fn=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Next-token cross-entropy (plus, at the model's weights, the expert
    layers' auxiliary losses and the predicted-ahead module's loss over
    the positions that have a target).  batch: {"tokens": (b, s+1) int32}
    or {"inputs": (b, s), "targets": (b, s)}; returns (loss, metrics).

    ``forward_fn(params, inputs) -> (logits, aux)`` overrides the forward
    pass (e.g. the pipelined path) so there is exactly one loss definition.
    """
    total, (metrics, _) = loss_and_counts(
        params, batch, cfg, mesh=mesh, rules=rules, forward_fn=forward_fn)
    return total, metrics


def _mean_nll(logits, targets, weights=None):
    """Mean next-token loss; ``weights (seq,)`` of 0 and 1 leaves the
    positions at 0 out of the mean."""
    # One row or many, one path: ``_row_nll``'s gradient is written out, so
    # no gather's gradient is left to compile to the flat scatter that a
    # one-row batch once had to dodge (PERF.md §6, PR 30 and PR 82).
    nll = _row_nll(logits, targets)
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / (jnp.sum(weights) * nll.size
                                     / weights.size)


def _weighted_nll(logits, targets, weights):
    """``sum(weights x nll) / positions``: the denoising loss, ``weights (b,
    s)`` the masked positions' ``1 / p`` and 0 elsewhere."""
    nll = _row_nll(logits, targets)
    return jnp.sum(nll * weights) / nll.size


def _is_target(logits, targets):
    """Where along the vocabulary each position's target sits: a compare
    against an iota, which XLA fuses into whatever reads it (and
    partitions over a sharded vocabulary), where a gather is an op of
    its own and its gradient a scatter."""
    return jax.lax.broadcasted_iota(
        targets.dtype, logits.shape, logits.ndim - 1) == targets[..., None]


@jax.custom_vjp
def _row_nll(logits, targets):
    """Each position's ``-log softmax(logits)[target]``, as ``logsumexp -
    the target's logit``: ONE pass over the vocabulary.  The gradient is
    written out (``_row_nll_bwd``) because JAX's own, of ``log_softmax``
    and a gather, keeps a float32 ``log p`` the size of the logits for
    the backward pass and reads the vocabulary twice more (PR 82)."""
    return _row_nll_fwd(logits, targets)[0]


def _row_nll_fwd(logits, targets):
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
    picked = jnp.sum(
        jnp.where(_is_target(logits, targets), logits, 0.0), axis=-1)
    return lse - picked, (logits, lse, targets)


def _row_nll_bwd(kept, g):
    """``(softmax - [target]) x g``: elementwise over the kept logits, so
    it fuses into the operands of the head's two backward products and no
    array of the logits' size is written for it."""
    logits, lse, targets = kept
    grad = jnp.exp(logits - lse[..., None]) \
        - _is_target(logits, targets).astype(logits.dtype)
    return grad * g[..., None], None


_row_nll.defvjp(_row_nll_fwd, _row_nll_bwd)
