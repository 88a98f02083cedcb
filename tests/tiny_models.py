"""The tiny models tier-1 builds, one row each: the ``LlamaConfig`` a model's
suite runs at CPU size, the plain reference under ``benchmark/reference``
with its configuration (the public key names), the tokens, how the
parameters are drawn — and ONE cached maker a process of a row's program
(``program``: configuration, parameters, and its loss, gradients and
per-token losses as compiled functions) and of what its reference says of
the same parameters (``reference``).  A new model is a row here, its
reference, and a file of tests of what is new in it.  Not collected by
pytest (no ``test_`` in its name); ``tests/test_blocks.py`` holds the table
to every registered block."""

import contextlib
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (
    afmoe, granite_hybrid, joyai_flash, keye_sparse, kimi_linear, lfm2_moe,
    mellum, nemotron3, nemotron_h, olmo_hybrid, olmoe, ouro_looped, phi4flash,
    sdar_block_diffusion, solar_open2, xing4)
from ray_tpu.models.llama import (
    ROPE_BY_KIND, LlamaConfig, forward, init_params, loss_fn)
from ray_tpu.ops.moe import moe_block

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
        params=functools.partial(seeded, draw=5), precision="highest"),
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
             first_expert=4)),
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
             mtp_loss_coef=0.3)),
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
             first_expert=0, mtp_loss_coef=0.3)),
    # the published layers in small: M, E, M, *, E; 2 groups of 4 heads; 16
    # experts of width 32 of which this chip holds 4..11, 3 a token; a
    # shared expert of width 48; 8 query heads over 2 KV heads
    "nemotron": Row(
        dict(_NEMOTRON, experts_held=8, num_selected=3,
             routed_scaling_factor=2.5),
        _jax_tokens(4, 33), nemotron_h,
        dict(_NEMOTRON_CONF, num_experts_per_tok=3,
             routed_scaling_factor=2.5),
        params=functools.partial(seeded, also=("D",)), precision="highest"),
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
        params=functools.partial(seeded, also=("D",)), precision="highest"),
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
             mup_enabled=True)),
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
             router_aux_loss_coef=0.001)),
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
        params=_keye_params, precision="highest"),
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
        precision="highest"),
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
        precision="highest"),
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
        precision="highest"),
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
        precision="highest"),
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


class Frozen(dict):
    """A dict that ``program`` / ``reference`` can take among their
    (hashed) overrides: a public file's nested group, never changed."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


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
