"""Operations and bytes ONE CHIP'S SHARE of a Kimi-Linear model needs, from
shapes alone: what ``"flops": "flops_kimi_linear"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``kda.scan_roofline``.

The model is the first ``num_hidden_layers`` layers, each a KDA or a latent
mixer as ``linear_attn_config``'s lists (counted from 1) say, a dense FFN in
the ``first_k_dense_replace`` leading layers and the expert layer after
(``num_experts`` the experts HELD, ``reduced`` their published count;
``vocab_size`` the slice).  Counted is what forward and backward REQUIRE of
this chip:

- 6 a matmul parameter and token: a KDA mixer's input projection ([q | k |
  v | the two low ranks | b]), its two up-projections and its output
  projection; a latent mixer's four matrices (q is ONE: ``q_lora_rank``
  null); the dense FFN; in an expert layer the router over ALL the
  published experts, the shared expert, and the HELD rows in expectation
  (``num_experts_per_token x held / published`` experts a token); the head
  over the slice;
- causal attention in the latent layers, 3 products over the q/k head (192)
  and 3 over ``v_head_dim``;
- the KDA RECURRENCE in its layers (``kda_flops_per_token``).

NOT counted: the convolution (4 taps a channel), norms, gates, softplus,
the decay's one multiplication a state element, the sort and gathers of the
dispatch, a layer run again under the checkpoint, and whatever a chunked
form of the rule computes beyond the recurrence (the chunk's decayed ``k
k^T`` and ``q k^T`` level by level, the triangular inverse, the products
that form the chunk's new values): that is the form's overhead, so no
reading of ``kda.scan_roofline`` passes 100.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = ["train_flops_per_token", "total_params", "flash_step_flops",
           "flash_step_bytes", "experts_step_flops", "experts_step_bytes",
           "kda_step_flops", "kda_step_bytes"]


def mixers(conf: Dict) -> List[str]:
    """The mixers of the layers that are run, in order."""
    kda = conf["linear_attn_config"]["kda_layers"]
    return ["kda" if i + 1 in kda else "latent"
            for i in range(conf["num_hidden_layers"])]


def kda_layers(conf: Dict) -> int:
    return mixers(conf).count("kda")


def latent_layers(conf: Dict) -> int:
    return mixers(conf).count("latent")


def expert_layers(conf: Dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("num_experts")
    return cut["published"] if cut else conf["num_experts"]


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_token"] * conf["num_experts"]
            / published_experts(conf))


def kda_inner(conf: Dict) -> int:
    linear = conf["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def kda_params(conf: Dict) -> int:
    """The four matrices of a KDA mixer: the input projection, the decay's
    and the gate's up-projections (the low ranks are a head's width), the
    output projection."""
    d, linear = conf["hidden_size"], conf["linear_attn_config"]
    inner, rank = kda_inner(conf), linear["head_dim"]
    return (d * (3 * inner + 2 * rank + linear["num_heads"])
            + 2 * rank * inner + inner * d)


def qk_dim(conf: Dict) -> int:
    return conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def latent_params(conf: Dict) -> int:
    """The four matrices of a latent mixer without a q rank."""
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return (d * heads * qk_dim(conf)
            + d * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            + conf["kv_lora_rank"] * heads * (conf["qk_nope_head_dim"]
                                              + conf["v_head_dim"])
            + heads * conf["v_head_dim"] * d)


def expert_params(conf: Dict) -> int:
    """The three SwiGLU matrices of ONE routed expert."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    dense = conf["first_k_dense_replace"] * 3 * d * conf["intermediate_size"]
    experts = expert_layers(conf) * (
        d * published_experts(conf)
        + (conf["num_shared_experts"] + held_per_token(conf))
        * expert_params(conf))
    return (kda_layers(conf) * kda_params(conf)
            + latent_layers(conf) * latent_params(conf) + dense + experts
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the chip's train state holds: the held experts, the
    embedding, every norm, the convolutions, ``A_log`` a head and
    ``dt_bias`` a key channel, the selection biases."""
    d, linear = conf["hidden_size"], conf["linear_attn_config"]
    inner = kda_inner(conf)
    kda = (kda_params(conf) + d + linear["short_conv_kernel_size"] * 3 * inner
           + inner + linear["num_heads"] + linear["head_dim"])
    latent = latent_params(conf) + d + conf["kv_lora_rank"]
    dense = 3 * d * conf["intermediate_size"] + d
    routed = published_experts(conf)
    expert = (d * routed + routed + d
              + (conf["num_shared_experts"] + conf["num_experts"])
              * expert_params(conf))
    return (kda_layers(conf) * kda + latent_layers(conf) * latent
            + conf["first_k_dense_replace"] * dense
            + expert_layers(conf) * expert + 2 * d * conf["vocab_size"] + d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the
    latent layers that are run (``flops.py`` has the derivation): the
    scores and their two gradients over the q/k head, the output and its
    two over v's."""
    return (3.0 * latent_layers(conf) * seq * conf["num_attention_heads"]
            * (qk_dim(conf) + conf["v_head_dim"]))


def kda_flops_per_token(conf: Dict) -> float:
    """The rule's recurrence, forward and backward, per token, in the KDA
    layers that are run.  A token and head, forward, with a state of keys x
    values: ``S'^T k`` (2 operations a state element), the rank-one update
    ``S' + beta k (v - S'^T k)^T`` (2) and ``S^T q`` (2): 6 x head_dim^2;
    the backward pass twice that.  The decay's one multiplication a state
    element is left out, as ``flops_olmo_hybrid.py`` leaves the scalar
    one's: the two rules' shares are on one scale."""
    linear = conf["linear_attn_config"]
    return (18.0 * kda_layers(conf) * linear["num_heads"]
            * linear["head_dim"] ** 2)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq)
            + kda_flops_per_token(conf))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x ``seq``
    tokens, forward and backward, in the latent layers that are run."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic that attention needs (``flops_xing4.py``'s count):
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv.  q is heads x the q/k head; k is what the MODEL has,
    heads x ``qk_nope_head_dim`` and the one shared 64-wide head; v and o
    heads x ``v_head_dim``."""
    heads, tokens = conf["num_attention_heads"], rows * seq
    q = tokens * heads * qk_dim(conf) * itemsize
    k = tokens * (heads * conf["qk_nope_head_dim"]
                  + conf["qk_rope_head_dim"]) * itemsize
    v = tokens * heads * conf["v_head_dim"] * itemsize
    return float(latent_layers(conf) * 3 * (q + k + 2 * v))


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every expert
    layer: each HELD row forward, the gradient to it and the gradient to
    its expert's weights."""
    return (6.0 * rows * seq * expert_layers(conf) * held_per_token(conf)
            * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_moe.py``'s count, over the experts and rows that are here)."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 3 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["num_experts"] * expert_params(conf) * itemsize
    return float(expert_layers(conf) * (row_bytes + weight_bytes))


def kda_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the rules of one train step need."""
    return kda_flops_per_token(conf) * rows * seq


def kda_pass_bytes(conf: Dict, rows: int, seq: int, itemsize: int = 2):
    """``(forward, backward)`` bytes of ONE KDA layer's rule: forward reads
    q, k, v, the log-decays ``(tokens, heads, head_dim)`` float32 and beta
    ``(tokens, heads)`` float32 and writes o; backward reads q, k, v, the
    two, and o's gradient and writes the gradients of q, k, v and the two."""
    tokens = rows * seq
    wide = tokens * kda_inner(conf)
    beta = tokens * conf["linear_attn_config"]["num_heads"] * 4
    forward = 4 * wide * itemsize + wide * 4 + beta
    backward = 7 * wide * itemsize + 2 * wide * 4 + 2 * beta
    return forward, backward


def kda_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the rules of one train step need, all KDA layers
    (``kda_pass_bytes``).  Not counted: anything a chunked form writes and
    reads back (the chunk's matrices, its new values, the entering states,
    a level's scaled operands)."""
    return float(kda_layers(conf) * sum(
        kda_pass_bytes(conf, rows, seq, itemsize)))


def kda_scan_ceiling_pct(conf: Dict, rows: int, seq: int) -> float:
    """The most ``kda.scan_roofline`` can read while the scope runs the
    forward pass twice (the layer checkpoint keeps nothing of the rule)."""
    forward, backward = kda_pass_bytes(conf, rows, seq)
    return 100.0 * (forward + backward) / (2 * forward + backward)
