"""Flagship model family: Llama-style decoder LM, TPU-first.  A layer is a
MIXER (softmax attention | latent attention | a Mamba-2 state-space mixer |
a gated delta-rule linear-attention mixer | a gated short convolution)
followed by an FFN (dense SwiGLU
| dropless experts with or without a shared expert), each on a RESIDUAL
(one stream that every block adds to | ``hc_mult`` streams mixed round
every block by learned doubly stochastic maps): mixer x FFN x residual, and
a model is a pattern of such layers, with or without a predicted-ahead
module behind them.

Pure-functional design: params are a pytree of arrays, every tensor
dimension has a *logical axis name*, and one rules table
(``parallel.sharding.DEFAULT_RULES``) maps names to mesh axes — so the same
model runs DP, FSDP, 2D (fsdp x tp), MoE-EP, or sequence-parallel by
swapping rules, never editing model code.

TPU-first choices:
- layers are *stacked* on a leading "layer" dim and driven by ``lax.scan``
  (+``jax.checkpoint``): one trace/compile of a single layer regardless of
  depth, rematerialized backward to trade FLOPs for HBM.
- bf16 activations/params with f32 RMSNorm stats and f32 logits/loss — the
  MXU-native recipe.
- attention is pluggable: pallas flash (ops/attention.py), ring over 'sp'
  (ops/ring_attention.py), Ulysses all-to-all, or the XLA reference — all
  numerically interchangeable (tested).
- MoE layers are dropless (ops/moe.py): every token reaches all of its
  ``num_selected`` experts through a grouped matmul over the sorted
  assignments; expert tensors are sharded over 'ep', each rank computes its
  own experts' rows inside a shard_map and the partial outputs are summed.
- a model whose layers differ (``layer_types``: granite-4.0-h's Mamba-2
  layers with an attention layer every tenth; ``leading_dense``: dense FFNs
  in the first layers of an expert model) is scanned by maximal RUNS of
  one kind (mixer, FFN), each run one ``lax.scan`` over its own stacked
  parameters, which hold only what that kind has (``params["layers"]`` is
  then a tuple of stacks, one a run); a model of one kind is one run and
  ``params["layers"]`` the one stack.
- latent attention (arXiv:2412.19437 §2.1.1): q and k/v come up from
  normed low-rank projections, a head's q and k are [no-position part |
  rotary part, the k's shared by all heads] and wider than its v; the flash
  kernels take the two head sizes.
- the n-stream residual (manifold-constrained hyper-connections,
  arXiv:2512.24880 §4): the scan carries ``(b, s, n * d)``; a block's two
  halves (its maps and input: ``hc_map``; the write back: ``hc_mix``) are
  ``ops/streams.py``'s operations, one read of the streams a pass each.
- one chip's share of a layer (``experts_held``, ``first_expert``): the
  router keeps its published width, the expert tensors hold the experts
  that live here, and what the absent ones would add is left out.
- a gated delta-rule mixer (``ops/delta.py``, arXiv:2412.06464) where
  ``layer_types`` says ``linear_attention``, beside ``full_attention``
  layers (the softmax mixer under its other public name), and the OLMo 2
  family's block, which norms what a block ADDS (``block_norm="output"``:
  ``x + norm(f(x))``) where every other model norms what it reads.
- a gated short-convolution mixer (``ops/ssm.py::gated_short_conv``: ``C
  * conv(B * x)``, three taps a channel, no bias, no activation) where
  ``layer_types`` says ``conv``, beside attention layers with a QK-norm
  over EACH head (``qk_head_norm``; ``qk_norm`` is over the whole
  projection) and expert layers behind leading dense ones: a model whose
  runs differ in mixer AND FFN (LFM2-8B-A1B: five scans at depth 8).

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
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops import attention, moe, streams
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.ops.layers import (
    rms_norm, rope, apply_rope, swiglu, repeat_kv_heads, yarn_inv_freq,
    yarn_mscale,
)
from ray_tpu.ops.delta import delta_chunked
from ray_tpu.ops.moe import moe_block, update_selection_bias
from ray_tpu.ops.ssm import (
    causal_conv1d, gated_rms_norm, gated_short_conv, ssd_chunked)
from ray_tpu.parallel.mesh import (
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_SP, AXIS_TP,
)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalAxisRules, with_logical_constraint,
)


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
    # The mixer of each layer, "attention" (or "full_attention") | "mamba"
    # | "linear_attention" | "conv"; only the first ``num_layers`` entries
    # are the model, empty = attention everywhere.
    layer_types: Tuple[str, ...] = ()
    ssm_heads: int = 0                # Mamba-2: heads x head_dim = inner width
    ssm_head_dim: int = 64
    ssm_state: int = 128              # state size a head (d_state)
    ssm_groups: int = 1               # groups that share B and C
    ssm_conv: int = 4                 # width of the causal depthwise conv
    ssm_chunk: int = 256              # tokens a chunk of the scan
    position_embedding: str = "rope"  # rope | nope (no position signal)
    attention_multiplier: Optional[float] = None  # None: head_dim ** -0.5
    embedding_multiplier: float = 1.0  # on the embedded tokens
    residual_multiplier: float = 1.0  # on what each block adds to the stream
    logits_scaling: float = 1.0       # logits are divided by it
    tie_embeddings: bool = False      # the head reads the embedding table
    # Latent attention: kv_lora_rank > 0 makes "latent" the mixer of a
    # model without layer_types.  A head's q and k are qk_nope_dim +
    # qk_rope_dim wide, its v and output v_head_dim.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Any = None          # the public file's YaRN group
    # Expert layers: mlp_dim is an expert's width.
    leading_dense: int = 0            # layers 0.. with a dense FFN instead
    dense_mlp_dim: int = 0            # their width (0: mlp_dim)
    experts_held: int = 0             # of num_experts, here (0: all)
    first_expert: int = 0             # the first one held
    shared_experts: int = 0           # experts every token meets
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
    # The gated delta-rule mixer: gdn_heads heads whose q and k are
    # gdn_key_dim wide and whose v and output gdn_value_dim.
    gdn_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4                 # width of the causal depthwise conv
    gdn_neg_eigval: bool = False      # beta in (0, 2): eigenvalues (-1, 1)
    sconv_width: int = 3              # taps of the gated short convolution
    # Where a block's RMSNorm sits: "input", x + f(norm(x)), or "output",
    # x + norm(f(x)) with the same weight on what the block adds.
    block_norm: str = "input"

    def __post_init__(self):
        # a configuration file hands a list: keep the config hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.router_groups != 1 or self.num_nextn > 1:
            raise NotImplementedError(
                "group-limited routing (n_group > 1) and more than one "
                "predicted-ahead module are not implemented")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring {self.router_scoring!r}")
        if self.block_norm not in ("input", "output"):
            raise ValueError(f"block_norm {self.block_norm!r}")
        if self.block_norm == "output" and (
                self.num_experts or {"mamba", "conv"} & set(self.layer_types)):
            raise NotImplementedError(
                "block_norm='output' is implemented for the softmax, latent "
                "and delta-rule mixers and the dense FFN")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm is over the whole projection, "
                             "qk_head_norm over each head: one of the two")
        unknown = set(self.layer_types) - set(_MIXERS)
        if unknown:
            raise ValueError(
                f"layer_types {sorted(unknown)}: not in {sorted(_MIXERS)}")
        if self.layer_types and len(self.layer_types) < self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_layers is {self.num_layers}")

    @property
    def qkv_dim(self) -> int:
        return self.num_heads * self.head_dim

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
    def latent_qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def dense_width(self) -> int:
        return self.dense_mlp_dim or self.mlp_dim

    @property
    def local_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def select_bias(self) -> bool:
        return self.topk_method == "noaux_tc"

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, FFN) of every layer: the mixer ``layer_types`` names
        (latent attention for a model with a ``kv_lora_rank``, else
        attention), a dense FFN in the ``leading_dense`` first layers and
        in a model without experts, the expert layer elsewhere."""
        mixers = self.layer_types[:self.num_layers] or (
            ("latent" if self.kv_lora_rank else "attention",)
            * self.num_layers)
        return tuple(
            (mixer, "moe" if self.num_experts and i >= self.leading_dense
             else "dense") for i, mixer in enumerate(mixers))

    @property
    def kind_runs(self) -> Tuple[Tuple[Tuple[str, str], int], ...]:
        """The model as maximal runs of one kind of layer:
        (((mixer, FFN), layers), ...)."""
        runs = []
        for kind in self.layer_kinds:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return tuple((kind, n) for kind, n in runs)

    @property
    def layer_runs(self) -> Tuple[Tuple[str, int], ...]:
        """``kind_runs`` by the mixer alone: ((mixer, layers), ...)."""
        return tuple((mixer, n) for (mixer, _), n in self.kind_runs)

    @property
    def mtp_runs(self):
        """The predicted-ahead module's block: one expert layer (a dense
        one in a model without experts)."""
        mixer = self.layer_kinds[-1][0]
        return (((mixer, "moe" if self.num_experts else "dense"), 1),)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(embed_dim=5120, num_layers=40, num_heads=40,
                           num_kv_heads=40, mlp_dim=13824, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """CI-sized config: runs on one CPU device in seconds."""
        defaults = dict(vocab_size=256, embed_dim=64, num_layers=2,
                        num_heads=4, num_kv_heads=4, head_dim=16, mlp_dim=128,
                        max_seq_len=64, dtype=jnp.float32, remat=False,
                        attn_impl="reference")
        defaults.update(kw)
        return LlamaConfig(**defaults)


def _attention_shapes(cfg: LlamaConfig):
    d, h, kvd = cfg.embed_dim, cfg.qkv_dim, cfg.kv_dim
    shapes = {
        "attn_norm": ((d,), ("layer", "embed")),
        "wq": ((d, h), ("layer", "kernel_in", "heads")),
        "wk": ((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wv": ((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wo": ((h, d), ("layer", "heads", "kernel_in")),
    }
    if cfg.qk_norm:  # over the whole projection, before heads and RoPE
        shapes.update({"q_norm": ((h,), ("layer", "heads")),
                       "k_norm": ((kvd,), ("layer", "kv_heads"))})
    if cfg.qk_head_norm:  # over each head, ONE weight of a head's size
        shapes.update({"q_norm": ((cfg.head_dim,), ("layer", "head_dim")),
                       "k_norm": ((cfg.head_dim,), ("layer", "head_dim"))})
    return shapes


def _latent_shapes(cfg: LlamaConfig):
    """Latent attention: ``wq_a``/``wq_b`` take q down to ``q_lora_rank``
    and up to heads x [nope | rope]; ``wkv_a`` gives [the latent c_kv | the
    one rotary k every head shares], ``wkv_b`` takes the normed latent up
    to heads x [k_nope | v] (the published layouts of ``kv_a_proj_with_mqa``
    and ``kv_b_proj``)."""
    d, heads, qk = cfg.embed_dim, cfg.num_heads, cfg.latent_qk_dim
    return {
        "attn_norm": ((d,), ("layer", "embed")),
        "wq_a": ((d, cfg.q_lora_rank), ("layer", "kernel_in", None)),
        "q_a_norm": ((cfg.q_lora_rank,), ("layer", None)),
        "wq_b": ((cfg.q_lora_rank, heads * qk), ("layer", None, "heads")),
        "wkv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_dim),
                  ("layer", "kernel_in", None)),
        "kv_a_norm": ((cfg.kv_lora_rank,), ("layer", None)),
        "wkv_b": ((cfg.kv_lora_rank,
                   heads * (cfg.qk_nope_dim + cfg.v_head_dim)),
                  ("layer", None, "heads")),
        "wo": ((heads * cfg.v_head_dim, d), ("layer", "heads", "kernel_in")),
    }


def _mamba_shapes(cfg: LlamaConfig):
    """A Mamba-2 mixer: ``ssm_in`` gives [z | x B C | dt] side by side
    (the published layout of ``in_proj``); the convolution runs over
    x, B and C; ``dt_bias``, ``A_log`` and ``D`` are a number a head."""
    d, inner, conv = cfg.embed_dim, cfg.ssm_inner, cfg.ssm_conv_dim
    return {
        "ssm_norm": ((d,), ("layer", "embed")),
        "ssm_in": ((d, inner + conv + cfg.ssm_heads),
                   ("layer", "kernel_in", "ssm_inner")),
        "conv_w": ((cfg.ssm_conv, conv), ("layer", None, "ssm_inner")),
        "conv_b": ((conv,), ("layer", "ssm_inner")),
        "dt_bias": ((cfg.ssm_heads,), ("layer", None)),
        "A_log": ((cfg.ssm_heads,), ("layer", None)),
        "D": ((cfg.ssm_heads,), ("layer", None)),
        "gate_norm": ((inner,), ("layer", "ssm_inner")),
        "ssm_out": ((inner, d), ("layer", "ssm_inner", "kernel_in")),
    }


def _delta_shapes(cfg: LlamaConfig):
    """A gated delta-rule mixer: ``gdn_in`` gives [q | k | v | gate | a |
    b] side by side (``a`` the decay's input and ``b`` the write
    strength's, a number a head each); the convolution runs over q, k and
    v; ``gdn_dt_bias`` and ``gdn_A_log`` are a number a head,
    ``gdn_gate_norm`` ONE weight of a head's value size."""
    d, keys, values = cfg.embed_dim, cfg.gdn_key_inner, cfg.gdn_value_inner
    return {
        "gdn_norm": ((d,), ("layer", "embed")),
        "gdn_in": ((d, 2 * keys + 2 * values + 2 * cfg.gdn_heads),
                   ("layer", "kernel_in", "gdn_inner")),
        "gdn_conv_w": ((cfg.gdn_conv, cfg.gdn_conv_dim),
                       ("layer", None, "gdn_inner")),
        "gdn_dt_bias": ((cfg.gdn_heads,), ("layer", None)),
        "gdn_A_log": ((cfg.gdn_heads,), ("layer", None)),
        "gdn_gate_norm": ((cfg.gdn_value_dim,), ("layer", None)),
        "gdn_out": ((values, d), ("layer", "gdn_inner", "kernel_in")),
    }


def _conv_shapes(cfg: LlamaConfig):
    """A gated short-convolution mixer: ``sconv_in`` gives [B | C | x]
    side by side, each as wide as the model (the published layout of
    ``in_proj``); the convolution has ``sconv_width`` taps a channel and
    no bias."""
    d = cfg.embed_dim
    return {
        "sconv_norm": ((d,), ("layer", "embed")),
        "sconv_in": ((d, 3 * d), ("layer", "kernel_in", "sconv_inner")),
        "sconv_w": ((cfg.sconv_width, d), ("layer", None, "sconv_inner")),
        "sconv_out": ((d, d), ("layer", "sconv_inner", "kernel_in")),
    }


def _dense_shapes(d: int, m: int, prefix: str = "w_"):
    return {
        prefix + "gate": ((d, m), ("layer", "kernel_in", "mlp")),
        prefix + "up": ((d, m), ("layer", "kernel_in", "mlp")),
        prefix + "down": ((m, d), ("layer", "mlp", "kernel_in")),
    }


def _ffn_shapes(cfg: LlamaConfig, ffn: str):
    """A dense SwiGLU, or the expert layer: the router over ALL the
    experts, the tensors of those held here, the selection bias (float32
    whatever the parameters': it moves by thousandths) and the shared
    expert where the model has them."""
    d, m = cfg.embed_dim, cfg.mlp_dim
    shapes = {"mlp_norm": ((d,), ("layer", "embed"))}
    if ffn == "dense":
        return {**shapes, **_dense_shapes(d, cfg.dense_width)}
    e, held = cfg.num_experts, cfg.local_experts
    shapes.update({
        "router": ((d, e), ("layer", "kernel_in", None)),
        "w_gate": ((held, d, m), ("layer", "expert", "kernel_in", "mlp")),
        "w_up": ((held, d, m), ("layer", "expert", "kernel_in", "mlp")),
        "w_down": ((held, m, d), ("layer", "expert", "mlp", "kernel_in")),
    })
    if cfg.select_bias:
        shapes["router_bias"] = ((e,), ("layer", None))
    if cfg.shared_experts:
        shapes.update(_dense_shapes(d, cfg.shared_experts * m, "shared_"))
    return shapes


def _residual_shapes(cfg: LlamaConfig):
    """The maps of the n-stream residual, a set for each of a layer's two
    blocks: one projection of the normed streams to [pre (n) | post (n) |
    res (n x n, row-major)], its bias, and the three scales."""
    n = cfg.hc_mult
    if n == 1:
        return {}
    maps = 2 * n + n * n
    shapes = {}
    for block in ("attn", "ffn"):
        shapes.update({
            f"hc_{block}_proj": ((n * cfg.embed_dim, maps),
                                 ("layer", None, None)),
            f"hc_{block}_bias": ((maps,), ("layer", None)),
            f"hc_{block}_scale": ((3,), ("layer", None))})
    return shapes


_MIXER_SHAPES = {"attention": _attention_shapes, "latent": _latent_shapes,
                 "mamba": _mamba_shapes, "full_attention": _attention_shapes,
                 "linear_attention": _delta_shapes, "conv": _conv_shapes}


def _layer_shapes(cfg: LlamaConfig, kind=("attention", "dense")
                  ) -> Dict[str, Tuple[Tuple[int, ...], Tuple]]:
    """name -> (shape-per-layer, logical axes incl. the stacked 'layer'
    dim) of a layer of this kind (mixer, FFN): the mixer's tensors, the
    FFN's, the residual's maps."""
    mixer, ffn = kind
    return {**_MIXER_SHAPES[mixer](cfg), **_ffn_shapes(cfg, ffn),
            **_residual_shapes(cfg)}


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
    return {"h_norm": ((d,), ("embed",)), "e_norm": ((d,), ("embed",)),
            "proj": ((2 * d, d), (None, "kernel_in")),
            "final_norm": ((d,), ("embed",))}


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    def stacks(runs):
        return _per_run([
            {k: ax for k, (_, ax) in _layer_shapes(cfg, kind).items()}
            for kind, _ in runs])

    axes = {
        "embed": ("vocab", "kernel_in"),
        "layers": stacks(cfg.kind_runs),
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("kernel_in", "vocab")
    if cfg.num_nextn:
        axes["mtp"] = {**{k: ax for k, (_, ax) in _mtp_shapes(cfg).items()},
                       "layers": stacks(cfg.mtp_runs)}
    return axes


# A recurrent mixer's tensors that are no projection: what each is, and
# the ``LlamaConfig`` field that holds its convolution's width.
_SSM_INIT = {
    "conv_w": ("conv", "ssm_conv"), "conv_b": ("conv", "ssm_conv"),
    "dt_bias": ("dt", None), "A_log": ("A", None), "D": ("D", None),
    "gdn_conv_w": ("conv", "gdn_conv"), "gdn_dt_bias": ("dt", None),
    "gdn_A_log": ("A", None), "sconv_w": ("conv", "sconv_width")}


def _ssm_init(name: str, key: jax.Array, shape, cfg: LlamaConfig):
    """The Mamba-2 reference code's initialisation of what is not a
    projection (``_SSM_INIT``; the delta-rule mixer's likewise): A uniform
    in 1..16 (kept as its log), dt log-uniform in 1e-3..1e-1 through the
    inverse of the softplus it passes, D = 1, the convolution as torch's
    ``Conv1d`` (uniform within 1/sqrt(width))."""
    what, width = _SSM_INIT[name]
    if what == "D":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if what == "A":
        return jnp.log(1.0 + 15.0 * u)
    if what == "dt":
        dt = jnp.maximum(jnp.exp(jnp.log(1e-3) + u * jnp.log(1e2)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return (2.0 * u - 1.0) * getattr(cfg, width) ** -0.5


def _map_init(name: str, key: jax.Array, shape, cfg: LlamaConfig):
    """The residual's maps start NEAR the plain residual and not AT it
    (a comparison with a reference could not see a map that is the
    identity, nor a uniform one): a block reads about the streams' mean
    (pre: sigmoid(-ln(n - 1)) = 1 / n each), writes to every stream (post:
    2 sigmoid(0) = 1), and a stream mostly keeps itself (res: 4 on the
    diagonal before exp and Sinkhorn, 0.95 after), each bias with normal
    noise of 0.1 on it; the three scales are 1, so the part that depends
    on the token is of the order of the bias.  The selection bias is drawn
    at 0.02 for the same reason (a trained one starts at 0)."""
    if name == "router_bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return jnp.ones(shape, jnp.float32)
    n = cfg.hc_mult
    static = jnp.concatenate([
        jnp.full((n,), -jnp.log(n - 1.0)), jnp.zeros((n,)),
        4.0 * jnp.eye(n).reshape(-1)])
    return static + 0.1 * jax.random.normal(key, shape, jnp.float32)


def _is_map(name: str) -> bool:
    return name == "router_bias" or (
        name.startswith("hc_") and not name.endswith("_proj"))


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Scaled-normal init (fan-in), params in ``cfg.param_dtype``.  A tied
    table is initialised as the head it also is (fan-in: the step-0 loss
    is then ln(vocab) to a hundredth)."""
    def run_shapes(runs):
        return [(n, _layer_shapes(cfg, kind)) for kind, n in runs]

    main = run_shapes(cfg.kind_runs)
    mtp = run_shapes(cfg.mtp_runs) if cfg.num_nextn else []
    n_tensors = sum(len(shapes) for _, shapes in main + mtp) + 3 + (
        len(_mtp_shapes(cfg)) if mtp else 0)
    keys = iter(jax.random.split(key, n_tensors))

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.param_dtype)

    def stack(n, shapes):
        layers = {}
        for name, (shape, _) in shapes.items():
            full = (n,) + shape
            if name.endswith("norm"):
                layers[name] = jnp.ones(full, cfg.param_dtype)
            elif name in _SSM_INIT:
                layers[name] = _ssm_init(name, next(keys), full, cfg).astype(
                    cfg.param_dtype)
            elif _is_map(name):
                layers[name] = _map_init(name, next(keys), full, cfg).astype(
                    jnp.float32 if name == "router_bias"
                    else cfg.param_dtype)
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                layers[name] = norm_init(next(keys), full, fan_in)
        return layers

    layers = _per_run([stack(n, shapes) for n, shapes in main])
    params = {
        "embed": norm_init(next(keys), (cfg.vocab_size, cfg.embed_dim),
                           cfg.embed_dim if cfg.tie_embeddings else 1.0),
        "layers": layers,
        "final_norm": jnp.ones((cfg.embed_dim,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(
            next(keys), (cfg.embed_dim, cfg.vocab_size), cfg.embed_dim)
    if mtp:
        params["mtp"] = {
            name: (jnp.ones(shape, cfg.param_dtype) if name.endswith("norm")
                   else norm_init(next(keys), shape, shape[-2]))
            for name, (shape, _) in _mtp_shapes(cfg).items()}
        params["mtp"]["layers"] = _per_run(
            [stack(n, shapes) for n, shapes in mtp])
    return params


def _sm_scale(cfg: LlamaConfig) -> float:
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    if not cfg.kv_lora_rank:
        return cfg.head_dim ** -0.5
    # latent attention: over the whole q/k head, times the square of
    # YaRN's temperature where the model states ``mscale_all_dim``
    scaling = dict(cfg.rope_scaling or ())
    return cfg.latent_qk_dim ** -0.5 * yarn_mscale(
        scaling.get("factor", 1.0), scaling.get("mscale_all_dim", 0.0)) ** 2


def _rope_inv_freq(cfg: LlamaConfig, dim: int):
    """YaRN's frequencies where the model's ``rope_scaling`` is of that
    type, else None (the plain ones)."""
    scaling = dict(cfg.rope_scaling or ())
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        return None
    return yarn_inv_freq(
        dim, cfg.rope_theta, factor=scaling["factor"],
        original=scaling["original_max_position_embeddings"],
        beta_fast=scaling.get("beta_fast", 32.0),
        beta_slow=scaling.get("beta_slow", 1.0))


def _attention(q, k, v, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Dispatch to the configured attention impl.

    Pallas kernels have no SPMD partitioning rule, so under a mesh the flash
    path runs inside shard_map (batch over (dp,fsdp), heads over tp); ring /
    ulysses manage the 'sp' axis themselves.
    """
    impl, scale = cfg.attn_impl, _sm_scale(cfg)
    if mesh is None:
        # Ring/ulysses degenerate to plain attention on one device.
        if impl == "flash":
            return flash_attention(q, k, v, causal=True, sm_scale=scale)
        k, v = repeat_kv_heads(q, k, v)
        return mha_reference(q, k, v, causal=True, sm_scale=scale)
    if impl == "ring":
        return ring_attention(q, k, v, causal=True, sm_scale=scale, mesh=mesh)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, causal=True, sm_scale=scale,
                                 mesh=mesh)
    if impl == "reference":
        return mha_reference(q, k, v, causal=True, sm_scale=scale)
    # flash under a mesh: pallas has no SPMD partitioning rule, so run the
    # kernel per-shard: batch over (dp,fsdp), heads over tp, seq replicated.
    # Manual over EVERY mesh axis — the TPU lowering refuses a Mosaic
    # kernel in a region that leaves any axis to the partitioner.
    from ray_tpu.parallel.sharding import manual_shard_map
    k, v = repeat_kv_heads(q, k, v)
    spec = P((AXIS_DP, AXIS_FSDP), None, AXIS_TP, None)
    fn = manual_shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True,
                                           sm_scale=scale),
        set(mesh.axis_names), in_specs=(spec, spec, spec),
        out_specs=spec, mesh=mesh)
    return fn(q, k, v)


def _attention_sp_manual(q, k, v, cfg: LlamaConfig):
    """Attention inside an already-manual 'sp' region (pipeline path):
    call the sharded bodies inline — no nested shard_map."""
    from ray_tpu.ops.ring_attention import _ring_attention_sharded
    from ray_tpu.ops.ulysses import _ulysses_sharded
    k, v = repeat_kv_heads(q, k, v)
    if cfg.attn_impl == "ulysses":
        return _ulysses_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP,
                                use_flash=False)
    return _ring_attention_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (batch, seq) int32 -> (logits f32 (b, s, vocab), aux_loss).

    Global-view path: call under jit with a mesh context; sharding
    constraints steer XLA's partitioner.  (The pipeline-parallel path is
    ``parallel.pipeline.forward_pipelined`` — manual SPMD.)
    """
    h, aux, _ = _hidden(params, tokens, cfg, mesh, rules)
    return (_lm_head(params, h, cfg, _make_cst(mesh, rules)),
            _mean_aux(aux, cfg, _expert_layers(cfg.kind_runs)))


def _embed(params, tokens, cfg: LlamaConfig, mesh, cst):
    """The tokens' embeddings (inside the scope ``embed``)."""
    if mesh is not None:
        # One-hot matmul instead of gather: with a ('vocab','embed')-
        # sharded table this lowers to a local matmul + psum over 'tp'
        # — the gather form makes the SPMD partitioner fully
        # rematerialize the table.
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
        x = onehot @ params["embed"].astype(cfg.dtype)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    return cst(_scaled(x, cfg.embedding_multiplier),
               ("batch", "seq", "embed"))


def _hidden(params, tokens, cfg: LlamaConfig, mesh, rules):
    """Embedding and layers: ``(h (b, s, d) before the last norm, aux,
    each run's per-layer expert counts or None)``."""
    cst = _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        x = _to_streams(_embed(params, tokens, cfg, mesh, cst), cfg)
    x, aux, counts = _scan_layers(params["layers"], x, cfg, mesh, rules)
    return _from_streams(x, cfg), aux, counts


def _scaled(x, multiplier: float):
    """``x * multiplier``; a multiplier of 1 adds no op to the program."""
    return x if multiplier == 1.0 else x * multiplier


def _scan_layers(layers, x, cfg: LlamaConfig, mesh, rules,
                 sp_manual: bool = False, aux=None, runs=None):
    """The layers over ``x`` (the streams side by side where the model has
    several): one ``lax.scan`` a run of one kind of layer (``runs``:
    ``cfg.kind_runs``), over that run's stacked parameters, each under the
    layer checkpoint.  Returns ``(x, aux, counts)``: ``counts`` holds, a
    run, what its layers hand out of the scan — the experts' assignments
    ``(layers, E)`` of a run whose router has a selection bias, else None."""
    carry = (x, _zero_aux(cfg) if aux is None else aux)
    counts = []
    for kind, stacked in _runs(layers, cfg.kind_runs if runs is None
                               else runs):
        layer_fn = _make_layer_fn(cfg, mesh, rules, sp_manual, kind)
        if cfg.remat:
            layer_fn = _checkpoint(layer_fn)
        carry, out = jax.lax.scan(layer_fn, carry, stacked)
        counts.append(out)
    return (*carry, counts)


# What the layer checkpoint keeps of a Mamba layer: the input projection's
# output [z | xBC | dt] (bf16, 139 MB a layer at 8192 tokens).  With it the
# backward pass runs no second ``ssm_in`` matmul; the convolution, the
# scan and the gated norm ARE run again (their intermediates are several
# times that size).  On the v5e: 8.8 ms of a 507 ms step for 1.25 GB held,
# 2.4 GB of program (PERF.md §6, PR 30).
MAMBA_SAVED_RESIDUALS = ("ssm_proj",)
# ... and of a delta-rule layer, likewise: [q | k | v | gate | a | b]
# (bf16, 142 MB a layer at 4096 tokens of the published 17340 columns).
DELTA_SAVED_RESIDUALS = ("gdn_proj",)


def _checkpoint(layer_fn):
    """The layer checkpoint of every path (``cfg.remat``): the backward
    pass recomputes the layer from its input, except the few residuals
    that are dear to recompute and cheap to hold, named where they are
    made — the flash kernel's output and log-sum-exp, an expert layer's
    row index (its sorts' results), a Mamba or delta-rule layer's input
    projection.  A
    layer that never produces a name (reference attention, a dense FFN)
    saves nothing under it."""
    return jax.checkpoint(
        layer_fn, policy=jax.checkpoint_policies.save_only_these_names(
            *attention.SAVED_RESIDUALS, *moe.SAVED_RESIDUALS,
            *MAMBA_SAVED_RESIDUALS, *DELTA_SAVED_RESIDUALS))


def _zero_aux(cfg: LlamaConfig):
    """What the layer scan carries beside the activations: a dense model's
    auxiliary loss (0), or float32 scalars by name: the expert layers',
    and the largest state a delta-rule layer saw."""
    zero = jnp.zeros((), jnp.float32)
    delta = "linear_attention" in cfg.layer_types[:cfg.num_layers]
    if not cfg.num_experts and not delta:
        return zero
    aux = {"aux_loss": zero}
    if cfg.num_experts:
        aux.update(z_loss=zero, load_max_over_mean=zero, dropped=zero,
                   rows_visited_share=zero, token_rows_read_share=zero)
    if cfg.experts_held:  # one chip's share: how much of the rows is here
        aux["held_share"] = zero
    if delta:
        aux[GDN_STATE_ABSMAX] = zero
    return aux


def _expert_layers(runs) -> int:
    return sum(n for (_, ffn), n in runs if ffn == "moe")


def _mean_aux(aux, cfg: LlamaConfig, expert_layers: int):
    """The sums the scan carried, as means over the ``expert_layers`` that
    added to them (``dropped`` stays a sum, the load and the delta-rule
    layers' state maxima)."""
    if not isinstance(aux, dict):
        return aux / cfg.num_layers
    return {k: v if k in ("load_max_over_mean", "dropped", GDN_STATE_ABSMAX)
            else v / max(expert_layers, 1) for k, v in aux.items()}


def _moe(x, lp, cfg: LlamaConfig, mesh: Optional[Mesh], cst,
         residual: bool = True):
    """The expert layer (``ops.moe.moe_block``) on the residual stream
    (its experts' sum alone without ``residual``).
    Under a mesh it runs per shard, as the flash kernel does: tokens over
    (dp, fsdp) x sp, experts over ep, their width over tp, partial outputs
    summed over ep x tp.  Inside a region that is already manual (the
    pipeline) it is called as it is and the partitioner splits it, which
    the TPU lowering refuses for a Mosaic kernel."""
    block = functools.partial(
        moe_block, num_selected=cfg.num_selected, norm_eps=cfg.norm_eps,
        norm_topk_prob=cfg.norm_topk_prob,
        topk_norm_eps=cfg.topk_norm_eps, scoring=cfg.router_scoring,
        gate_scale=cfg.routed_scaling_factor,
        first_expert=cfg.first_expert, residual=residual)
    bias = (lp["router_bias"],) if cfg.select_bias else ()
    args = (x, lp["mlp_norm"], lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"]) + bias
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return block(*args)
    from ray_tpu.parallel.sharding import manual_shard_map
    # The parameters as the region takes them, laid out under the scope
    # that uses them (pinned first as they are stored, or the partitioner
    # moves the change of layout up to the scan's slice): the fsdp gathers
    # and their gradients' scatters then carry a step scope like every
    # other collective.
    axes = {k: ax for k, (_, ax) in _ffn_shapes(cfg, "moe").items()}

    def laid_out(name, *gathered):
        return cst(cst(lp[name], axes[name][1:]), gathered)

    with jax.named_scope("moe_route"):
        small = (laid_out("mlp_norm", None), laid_out("router", None, None))
    with jax.named_scope("moe_experts"):
        args = (x,) + small + (
            laid_out("w_gate", "expert", None, "mlp"),
            laid_out("w_up", "expert", None, "mlp"),
            laid_out("w_down", "expert", "mlp", None)) + bias
    x_spec = P((AXIS_DP, AXIS_FSDP), AXIS_SP, None)
    up_spec = P(AXIS_EP, None, AXIS_TP)
    fn = manual_shard_map(
        functools.partial(block, token_axes=(AXIS_DP, AXIS_FSDP, AXIS_SP),
                          expert_axis=AXIS_EP, sum_axes=(AXIS_EP, AXIS_TP)),
        set(mesh.axis_names),
        in_specs=(x_spec, P(), P(), up_spec, up_spec,
                  P(AXIS_EP, AXIS_TP, None)) + (P(),) * len(bias),
        out_specs=(x_spec, P()), mesh=mesh)
    return fn(*args)


def _lm_head(params, x, cfg: LlamaConfig, cst):
    """Final norm and head product -> f32 logits (scope ``lm_head``)."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:  # one table, read twice: its gradient is
            # the sum of both uses
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(cfg.dtype))
        else:
            logits = x @ params["lm_head"].astype(cfg.dtype)
        logits = _scaled(logits.astype(jnp.float32),
                         1.0 / cfg.logits_scaling)
        return cst(logits, ("batch", "seq", "vocab"))


def _make_cst(mesh, rules):
    if mesh is None:
        return lambda x, ax: x
    return lambda x, ax: with_logical_constraint(x, ax, mesh=mesh,
                                                 rules=rules)


def _block_in(x, weight, cfg: LlamaConfig):
    """What a block reads: the stream through the block's norm, or, in a
    model that norms what a block adds (``_add``), the stream as it is."""
    if cfg.block_norm == "output":
        return x
    return rms_norm(x, weight, cfg.norm_eps)


def _add(x, y, cfg: LlamaConfig, cst, residual: bool, weight=None):
    """What a block hands on: the stream plus its output ``y`` (inside
    the block's last scope), or ``y`` alone where the layer mixes it into
    several streams itself.  ``weight`` is the block's norm: where the
    model norms what a block adds, it is applied here."""
    if cfg.block_norm == "output":
        y = rms_norm(y, weight, cfg.norm_eps)
    y = _scaled(cst(y, ("batch", "seq", "embed")), cfg.residual_multiplier)
    return x + y if residual else y


# A mixer, like an FFN, takes the stream and what the layer scan carries
# beside it (``_zero_aux``) and returns both: ``(x, aux, lp, cfg, mesh,
# cst, sp_manual, residual) -> (x, aux)``.  Only one that keeps a step
# statistic (``_delta_mixer``) touches ``aux``.


def _attend(x, aux, q, k, v, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
            residual: bool):
    """What every softmax mixer ends in: the attention itself (scope
    ``attention``), then the heads' outputs side by side through ``wo``
    and onto the stream (scope ``attn_out``)."""
    with jax.named_scope("attention"):
        if sp_manual:
            o = _attention_sp_manual(q, k, v, cfg)
        else:
            o = _attention(q, k, v, cfg, mesh)
    with jax.named_scope("attn_out"):
        o = o.reshape(*x.shape[:2], -1)
        return _add(x, o @ lp["wo"].astype(cfg.dtype), cfg, cst, residual,
                    lp["attn_norm"]), aux


def _attention_mixer(x, aux, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
                     residual: bool = True):
    """Softmax attention on the residual stream (scopes ``attn_qkv``,
    ``attention``, ``attn_out``)."""
    b, s = x.shape[0], x.shape[1]
    with jax.named_scope("attn_qkv"):
        h = _block_in(x, lp["attn_norm"], cfg)
        q = h @ lp["wq"].astype(cfg.dtype)
        k = h @ lp["wk"].astype(cfg.dtype)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_head_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        v = (h @ lp["wv"].astype(cfg.dtype)).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.position_embedding == "rope":
            offset = 0
            if sp_manual:
                offset = jax.lax.axis_index(AXIS_SP) * s
            cos, sin = rope(s, cfg.head_dim, cfg.rope_theta, offset=offset)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q = cst(q, ("batch", "seq", "heads", "head_dim"))
        k = cst(k, ("batch", "seq", "kv_heads", "head_dim"))
    return _attend(x, aux, q, k, v, lp, cfg, mesh, cst, sp_manual, residual)


def _latent_mixer(x, aux, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
                  residual: bool = True):
    """Latent attention on the residual stream (arXiv:2412.19437 §2.1.1)
    under the scopes of ``_attention_mixer``: ``attn_qkv`` holds both
    down-projections, their norms, both up-projections and RoPE.  A
    head's q and k are [no-position part | rotary part] — the k's rotary
    part is ONE head, shared by all and laid beside each head's own part
    in the one k the kernel reads — and its v is narrower; the softmax
    scale is over the whole q/k head."""
    b, s = x.shape[0], x.shape[1]
    heads, nope, rot = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("attn_qkv"):
        h = _block_in(x, lp["attn_norm"], cfg)
        q = (rms_norm(h @ lp["wq_a"].astype(cfg.dtype), lp["q_a_norm"],
                      cfg.norm_eps) @ lp["wq_b"].astype(cfg.dtype)).reshape(
                          b, s, heads, nope + rot)
        c_kv, k_rot = jnp.split(h @ lp["wkv_a"].astype(cfg.dtype),
                                [cfg.kv_lora_rank], -1)
        kv = (rms_norm(c_kv, lp["kv_a_norm"], cfg.norm_eps)
              @ lp["wkv_b"].astype(cfg.dtype)).reshape(
                  b, s, heads, nope + cfg.v_head_dim)
        offset = jax.lax.axis_index(AXIS_SP) * s if sp_manual else 0
        cos, sin = rope(s, rot, cfg.rope_theta, offset=offset,
                        inv_freq=_rope_inv_freq(cfg, rot))
        q = jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], -1)
        k_rot = apply_rope(k_rot[:, :, None, :], cos, sin)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot, (b, s, heads, rot))],
            -1)
        v = kv[..., nope:]
        q = cst(q, ("batch", "seq", "heads", "head_dim"))
        k = cst(k, ("batch", "seq", "heads", "head_dim"))
    return _attend(x, aux, q, k, v, lp, cfg, mesh, cst, sp_manual, residual)


def _mamba_mixer(x, aux, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
                 residual: bool = True):
    """A Mamba-2 mixer on the residual stream (``ops/ssm.py``): scopes
    ``ssm_in`` (norm, the one input projection, its split), ``ssm_conv``
    (the convolution over x, B, C with its SiLU; dt's softplus),
    ``ssm_scan`` (the chunked scan, ``D x`` included), ``ssm_out`` (the
    norm of the GATED output — gate first, then one norm over the whole
    inner width —, the output projection, the residual add).  The scan is
    Pallas kernels where its shapes allow, per shard of the batch."""
    b, s = x.shape[0], x.shape[1]
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["ssm_norm"], cfg.norm_eps)
        zxbcdt = checkpoint_name(h @ lp["ssm_in"].astype(cfg.dtype),
                                 *MAMBA_SAVED_RESIDUALS)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + cfg.ssm_conv_dim], -1)
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv1d(xbc, lp["conv_w"], lp["conv_b"])
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], -1)
    with jax.named_scope("ssm_scan"):
        y = _ssd_scan(mesh, sp_manual)(
            xs.reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim), dt,
            -jnp.exp(lp["A_log"].astype(f32)),
            bm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            cm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            lp["D"], chunk=cfg.ssm_chunk)
    with jax.named_scope("ssm_out"):
        y = gated_rms_norm(y.reshape(b, s, inner), z, lp["gate_norm"],
                           cfg.norm_eps)
        return _add(x, y @ lp["ssm_out"].astype(cfg.dtype), cfg, cst,
                    residual), aux


GDN_STATE_ABSMAX = "gdn_state_absmax"


def _delta_mixer(x, aux, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
                 residual: bool = True):
    """A gated delta-rule mixer on the residual stream (``ops/delta.py``;
    arXiv:2412.06464): scopes ``gdn_in`` (the block's norm where it norms
    its input, the one [q | k | v | gate | a | b] projection, its split),
    ``gdn_conv`` (the convolution over q, k, v with its SiLU, the L2 norm
    of each head's q and k — q then times ``key_dim ** -0.5`` —, ``beta =
    sigmoid(b)``, twice that where the rule may have negative eigenvalues,
    and the log-decay ``g = -exp(A_log) softplus(a + dt_bias)``),
    ``gdn_scan`` (the chunked rule, per shard of the batch), ``gdn_out``
    (each head's output through ONE RMSNorm weight of its value size, times
    SiLU of the gate; the output projection; the add).  ``aux`` keeps the
    largest state a layer saw at a chunk's end."""
    b, s = x.shape[0], x.shape[1]
    heads, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    keys, values = cfg.gdn_key_inner, cfg.gdn_value_inner
    f32 = jnp.float32
    with jax.named_scope("gdn_in"):
        h = _block_in(x, lp["gdn_norm"], cfg)
        proj = checkpoint_name(h @ lp["gdn_in"].astype(cfg.dtype),
                               *DELTA_SAVED_RESIDUALS)
        qkv, gate, a, bt = jnp.split(
            proj, [cfg.gdn_conv_dim, cfg.gdn_conv_dim + values,
                   cfg.gdn_conv_dim + values + heads], -1)
    with jax.named_scope("gdn_conv"):
        qkv = causal_conv1d(qkv, lp["gdn_conv_w"])
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
        o, peak = _delta_scan(mesh, sp_manual)(
            q, k, v.reshape(b, s, heads, dv), g, beta)
    with jax.named_scope("gdn_out"):
        o = (rms_norm(o.astype(f32), lp["gdn_gate_norm"], cfg.norm_eps)
             * jax.nn.silu(gate.reshape(b, s, heads, dv).astype(f32))
             ).astype(cfg.dtype)
        return _add(x, o.reshape(b, s, values) @ lp["gdn_out"].astype(
            cfg.dtype), cfg, cst, residual, lp["gdn_norm"]), {
                **aux, GDN_STATE_ABSMAX: jnp.maximum(
                    aux[GDN_STATE_ABSMAX], peak)}


def _conv_mixer(x, aux, lp, cfg: LlamaConfig, mesh, cst, sp_manual,
                residual: bool = True):
    """A gated short-convolution mixer on the residual stream (LFM2's
    ``conv`` layers; ``ops/ssm.py::gated_short_conv``): scopes ``sconv_in``
    (norm, the one [B | C | x] projection), ``sconv_gate`` (``C * conv(B *
    x)``: a causal depthwise convolution of ``sconv_width`` taps with no
    bias and no activation between two elementwise gates), ``sconv_out``
    (the output projection, the add).  Its state is the convolution's
    tail alone, ``sconv_width - 1`` tokens; elementwise and local in time,
    so under a mesh the partitioner splits it by rows as it does a norm
    (``sconv_inner`` maps to no mesh axis); not under a split of the
    sequence."""
    if sp_manual:
        raise NotImplementedError(
            "the short convolution needs the tail of the sequence shard "
            "before its own: not under a manual 'sp' region")
    with jax.named_scope("sconv_in"):
        h = rms_norm(x, lp["sconv_norm"], cfg.norm_eps)
        bcx = cst(h @ lp["sconv_in"].astype(cfg.dtype),
                  ("batch", "seq", "sconv_inner"))
    with jax.named_scope("sconv_gate"):
        y = gated_short_conv(bcx, lp["sconv_w"])
    with jax.named_scope("sconv_out"):
        return _add(x, y @ lp["sconv_out"].astype(cfg.dtype), cfg, cst,
                    residual), aux


def _swiglu_ffn(h, lp, cfg: LlamaConfig, prefix: str = "w_"):
    return swiglu(h @ lp[prefix + "gate"].astype(cfg.dtype),
                  h @ lp[prefix + "up"].astype(cfg.dtype)
                  ) @ lp[prefix + "down"].astype(cfg.dtype)


def _dense_ffn(x, aux, lp, cfg: LlamaConfig, mesh, cst,
               residual: bool = True):
    """-> (the stream, aux, nothing handed out of the scan)."""
    with jax.named_scope("ffn"):
        h = _block_in(x, lp["mlp_norm"], cfg)
        return _add(x, _swiglu_ffn(h, lp, cfg), cfg, cst, residual,
                    lp["mlp_norm"]), aux, None


def _moe_ffn(x, aux, lp, cfg: LlamaConfig, mesh, cst, residual: bool = True):
    """The expert layer (its own four scopes in place of ``ffn``) and,
    where the model has one, the shared expert, which every token meets
    (scope ``ffn``).  Hands the experts' assignments out of the scan where
    a selection bias is moved by them."""
    out, stats = _moe(x, lp, cfg, mesh, cst, residual)
    out = cst(out, ("batch", "seq", "embed"))
    if cfg.shared_experts:
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            out = out + cst(_swiglu_ffn(h, lp, cfg, "shared_"),
                            ("batch", "seq", "embed"))
    aux = {k: (jnp.maximum if k == "load_max_over_mean"
               else jnp.add)(v, stats[k]) if k in stats else v
           for k, v in aux.items()}
    return out, aux, stats["counts"] if cfg.select_bias else None


_MIXERS = {"attention": _attention_mixer, "latent": _latent_mixer,
           "mamba": _mamba_mixer, "full_attention": _attention_mixer,
           "linear_attention": _delta_mixer, "conv": _conv_mixer}
_FFNS = {"dense": _dense_ffn, "moe": _moe_ffn}


# ---- the n-stream residual (arXiv:2512.24880 §4) -------------------------
# The streams lie side by side, ``(b, s, n * d)``: stream j is the columns
# j * d .. (j + 1) * d, so ``vec X`` is the array as it lies and every
# slice starts on a lane tile.  (A (b, s, n, d) array would pad its n = 4
# rows to a 16-row tile.)
#
# Round every block the streams are read and written ONCE a pass, in their
# own dtype (``ops/streams.py``): ``streams_read`` makes a token's maps and
# the block's input from one read, ``streams_write`` writes the streams
# back from one read of them and of the block's output, and each has its
# backward pass written out — what goes round the block (``res^T dX'``)
# reaches ``streams_read``'s backward as a cotangent of the streams it
# handed on, and is added where ``dX`` is written.  No float32 or normed
# copy of the ``(tokens, n d)`` streams exists in memory in any pass
# (autodiff of the plain sums made four in the norm's gradient alone: 59
# of a step's 824 ms in ``xing4-train-s8192``, PERF.md §6, PR 37).  Where
# the shapes fit (``streams.kernels_fit``: d in whole lane blocks, tokens
# in tiles of 128, no mesh) the four bodies are Pallas kernels
# (``hc_read_fwd``, ``hc_read_bwd``, ``hc_write_fwd``, ``hc_write_bwd``);
# elsewhere the same sums as plain XLA under the same ``custom_vjp``.
# The layer checkpoint keeps nothing of either half: the rematerialised
# forward runs ``streams_read`` again (it also hands out the token's
# ``r (X proj)`` and ``r``, all its backward needs beside its arguments)
# and, of a layer's two blocks, the first one's ``streams_write``.

def _to_streams(x, cfg: LlamaConfig):
    """The embedded tokens copied to every stream (arXiv:2409.19606 §3)."""
    return x if cfg.hc_mult == 1 else jnp.tile(x, (1, 1, cfg.hc_mult))


def _stream(xs, j: int, cfg: LlamaConfig):
    d = xs.shape[-1] // cfg.hc_mult
    return xs[..., j * d:(j + 1) * d].astype(jnp.float32)


def _from_streams(xs, cfg: LlamaConfig):
    """The streams summed, for the last norm (arXiv:2409.19606 §3)."""
    if cfg.hc_mult == 1:
        return xs
    with jax.named_scope("hc_mix"):
        return sum(_stream(xs, j, cfg)
                   for j in range(cfg.hc_mult)).astype(cfg.dtype)


def _hc_plan(xs, cfg: LlamaConfig, kernels: bool):
    """What is static in a block's two halves (``streams.Plan``): the
    structure's numbers off the configuration, and the form off the
    shapes — ``kernels`` False (under a mesh, inside a manual region)
    keeps the XLA form whatever they are."""
    return streams.plan_for(
        xs, cfg.hc_mult, norm_eps=cfg.norm_eps,
        clamp=(cfg.hc_clamp_min, cfg.hc_clamp_max),
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        form=None if kernels else "xla")


def _hc_block(xs, lp, block: str, fn, cfg: LlamaConfig, kernels=True):
    """One block ``fn(x) -> (y, rest)`` on the streams ``xs``: ``X' = res
    X + post^T fn(pre X)``; returns ``(X', rest)``.  Before the block
    (scope ``hc_map``, ``streams.streams_read``) the token's maps — the
    streams normed as ONE vector of n d (no learned weight), projected by
    one matrix to [pre | post | res], scaled, biased; pre through a
    sigmoid, post through twice a sigmoid, res clipped and through the
    Sinkhorn rounds — and the block's input ``x = pre X``; after it (scope
    ``hc_mix``, ``streams.streams_write``) the write back.  The block
    opens its own scopes between them."""
    plan = _hc_plan(xs, cfg, kernels)
    with jax.named_scope("hc_map"):
        x, maps, xs = streams.streams_read(
            plan, xs, lp[f"hc_{block}_proj"], lp[f"hc_{block}_scale"],
            lp[f"hc_{block}_bias"])
    y, rest = fn(x)
    with jax.named_scope("hc_mix"):
        return streams.streams_write(plan, xs, y, maps), rest


def _make_layer_fn(cfg: LlamaConfig, mesh, rules, sp_manual: bool = False,
                   kind=("attention", "dense")):
    """One layer of ``kind`` (mixer, FFN) as a scan body over stacked
    layer params: a mixer (``_MIXERS``) then an FFN (``_FFNS``), each
    adding to the residual stream — or, in a model of several streams,
    each reading its input off them and written back into them through
    the layer's maps (``_hc_block``).  Shapes are read off the activation
    so the same body serves the full batch (forward) and microbatches
    (forward_pipelined).

    ``sp_manual``: the body runs inside a shard_map that is manual over
    'sp' (the pipeline path — jax/shardy cannot nest manual regions): the
    seq dim is device-local, RoPE uses the rank's global offset, and
    ring/ulysses attention run inline over the bound 'sp' axis.
    """
    cst = _make_cst(mesh, rules)
    mix, ffn = _MIXERS[kind[0]], _FFNS[kind[1]]
    # the streams' kernels take one chip's whole arrays (ops/streams.py)
    hc = functools.partial(_hc_block, cfg=cfg,
                           kernels=mesh is None and not sp_manual)

    def layer_fn(carry, lp):
        x, aux = carry
        x, aux = mix(x, aux, lp, cfg, mesh, cst, sp_manual)
        x, aux, out = ffn(x, aux, lp, cfg, mesh, cst)
        return (x, aux), out

    def streams_layer_fn(carry, lp):
        xs, aux = carry
        xs, aux = hc(xs, lp, "attn", lambda x: mix(
            x, aux, lp, cfg, mesh, cst, sp_manual, residual=False))

        def ffn_block(x):
            y, aux_, out = ffn(x, aux, lp, cfg, mesh, cst, residual=False)
            return y, (aux_, out)

        xs, (aux, out) = hc(xs, lp, "ffn", ffn_block)
        return (xs, aux), out

    return layer_fn if cfg.hc_mult == 1 else streams_layer_fn


def _one_kind(cfg: LlamaConfig, what: str) -> None:
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
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
        x = cst(_scaled(onehot @ params["embed"].astype(cfg.dtype),
                        cfg.embedding_multiplier),
                ("batch", "seq", "embed"))

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
    side and projected back to d (scope ``mtp_in``), go through one more
    layer of the module's own and its own last norm, and meet the model's
    head.  Position t then predicts token t + 2.  Returns ``(logits, aux,
    counts)`` as ``_hidden`` and the head do."""
    mp, cst = params["mtp"], _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        e = _embed(params, next_tokens, cfg, mesh, cst)
    with jax.named_scope("mtp_in"):
        x = jnp.concatenate([rms_norm(h, mp["h_norm"], cfg.norm_eps),
                             rms_norm(e, mp["e_norm"], cfg.norm_eps)], -1)
        x = cst(x @ mp["proj"].astype(cfg.dtype), ("batch", "seq", "embed"))
    with jax.named_scope("embed"):
        x = _to_streams(x, cfg)
    x, aux, counts = _scan_layers(mp["layers"], x, cfg, mesh, rules,
                                  aux=aux, runs=cfg.mtp_runs)
    logits = _lm_head(dict(params, final_norm=mp["final_norm"]),
                      _from_streams(x, cfg), cfg, cst)
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
                x = _scaled(jnp.take(sp["embed"], x, axis=0).astype(
                    cfg.dtype), cfg.embedding_multiplier)
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
                    forward_fn=None):
    """``loss_fn`` and, beside its metrics, what the train step needs and
    no metric can carry: ``(loss, (metrics, counts))``, ``counts`` the
    experts' assignments of every layer whose router has a selection bias
    (``{"layers": a run, "mtp": ...}``; ``update_router_bias`` reads it),
    None for a model without one."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    counts = ahead = None
    if forward_fn is None:
        h, aux, layer_counts = _hidden(params, inputs, cfg, mesh, rules)
        logits = _lm_head(params, h, cfg, _make_cst(mesh, rules))
        expert_layers = _expert_layers(cfg.kind_runs)
        if cfg.num_nextn:
            ahead, aux, mtp_counts = _predicted_ahead(
                params, h, targets, aux, cfg, mesh, rules)
            expert_layers += _expert_layers(cfg.mtp_runs)
        if cfg.select_bias:
            counts = {"layers": layer_counts,
                      "mtp": mtp_counts if cfg.num_nextn else None}
        aux = _mean_aux(aux, cfg, expert_layers)
    elif cfg.num_nextn or cfg.select_bias:
        raise NotImplementedError(
            "a predicted-ahead module and a selection bias need the layers' "
            "own outputs, which a replaced forward pass does not hand on")
    else:
        logits, aux = forward_fn(params, inputs)
    with jax.named_scope("loss"):
        loss = _mean_nll(logits, targets)
        if not cfg.num_experts:
            stats, aux = (aux, aux["aux_loss"]) if isinstance(
                aux, dict) else ({}, aux)
            total = loss + cfg.aux_loss_coef * aux
            metrics = {"loss": loss, "aux_loss": aux}
        else:
            stats = aux
            total = (loss + cfg.aux_loss_coef * aux["aux_loss"]
                     + cfg.z_loss_coef * aux["z_loss"])
            metrics = {"loss": loss, "aux_loss": aux["aux_loss"],
                       "z_loss": aux["z_loss"],
                       "moe_load_max_over_mean": aux["load_max_over_mean"],
                       "moe_dropped": aux["dropped"],
                       "moe_rows_visited_share": aux["rows_visited_share"],
                       "moe_token_rows_read_share":
                           aux["token_rows_read_share"]}
            if "held_share" in aux:
                metrics["moe_held_share"] = aux["held_share"]
        if GDN_STATE_ABSMAX in stats:
            metrics[GDN_STATE_ABSMAX] = stats[GDN_STATE_ABSMAX]
        if ahead is not None:
            # position t's target is token t + 2: the last has none
            seq = targets.shape[1]
            mtp_loss = _mean_nll(
                ahead, jnp.concatenate(
                    [targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1),
                (jnp.arange(seq) < seq - 1).astype(jnp.float32))
            total = total + cfg.mtp_loss_coef * mtp_loss
            metrics["mtp_loss"] = mtp_loss
        metrics["perplexity"] = jnp.exp(loss)
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
    if logits.shape[0] == 1:
        # One row: drop the degenerate dimension.  With it XLA's TPU
        # compiler turns the gradient of the gather below into a FLAT
        # scatter — float32 zeros the size of the logits, a relayout of
        # them and 5 GB more of temporaries at 8192 x 100352 (PERF.md §6,
        # PR 30).  Batches of several rows compile as they always have.
        logits, targets = logits[0], targets[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / (jnp.sum(weights) * nll.size
                                     / weights.size)


def _ssd_scan(mesh: Optional[Mesh], sp_manual: bool):
    """``ssd_chunked`` as ``_mamba_mixer`` calls it.  A Pallas kernel has
    no partitioning rule, so under a mesh the scan runs per shard of the
    batch, manual over EVERY axis as ``_attention`` runs flash: ``ssm_inner``
    maps to no mesh axis, so a shard holds whole heads and whole
    sequences.  Inside an already-manual region it is called inline."""
    if mesh is None or sp_manual:
        return ssd_chunked
    from ray_tpu.parallel.sharding import manual_shard_map

    def rows(ndim):
        return P((AXIS_DP, AXIS_FSDP), *(None,) * (ndim - 1))

    def per_shard(x, dt, a, bm, cm, d, *, chunk):
        return manual_shard_map(
            lambda *t: ssd_chunked(*t, chunk=chunk), set(mesh.axis_names),
            in_specs=(rows(4), rows(3), P(), rows(4), rows(4), P()),
            out_specs=rows(4), mesh=mesh)(x, dt, a, bm, cm, d)

    return per_shard


def _delta_scan(mesh: Optional[Mesh], sp_manual: bool):
    """``delta_chunked`` as ``_delta_mixer`` calls it: ``(o, the largest
    state at a chunk's end)``.  Under a mesh the rule runs per shard of the
    batch, as ``_ssd_scan`` runs the state-space scan: ``gdn_inner`` maps
    to no mesh axis, so a shard holds whole heads and whole sequences, and
    the statistic is the largest over the shards."""
    def rule(q, k, v, g, beta):
        o, _, peak = delta_chunked(q, k, v, g, beta)
        return o, peak

    if mesh is None or sp_manual:
        return rule
    from ray_tpu.parallel.sharding import manual_shard_map

    def rows(ndim):
        return P((AXIS_DP, AXIS_FSDP), *(None,) * (ndim - 1))

    def shard_rule(*t):
        o, peak = rule(*t)
        return o, jax.lax.pmax(peak, tuple(mesh.axis_names))

    return manual_shard_map(
        shard_rule, set(mesh.axis_names),
        in_specs=(rows(4), rows(4), rows(4), rows(3), rows(3)),
        out_specs=(rows(4), P()), mesh=mesh)
