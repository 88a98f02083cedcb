"""Operations and bytes ONE CHIP'S SHARE of a Solar-Open-2 model needs, from
shapes alone: what ``"flops": "flops_solar_open2"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``kda_neg.scan_roofline``.

The model is the first ``num_hidden_layers`` layers, a softmax mixer in
those ``gqa_layers`` lists (counted from 0) and a KDA mixer in every other,
each with the expert layer (``first_k_dense_replace`` 0;
``n_routed_experts`` the experts HELD, ``reduced`` their published count;
``vocab_size`` the slice).  Counted is what forward and backward REQUIRE of
this chip:

- 6 a matmul parameter and token: a KDA mixer's input projection ([q | k |
  v | the two low ranks | b]), its two up-projections and its output
  projection; a softmax mixer's q, k, v, output gate and output
  projections; in every layer the router over ALL the published experts,
  the shared expert, and the HELD rows in expectation
  (``num_experts_per_tok x held / published`` experts a token); the head
  over the slice;
- causal attention in the softmax layers, 6 products over ``head_dim``;
- the KDA RECURRENCE in its layers (``kda_flops_per_token``), counted as
  ``flops_kimi_linear.py`` counts it, whose counts of one layer's matrices
  and bytes are imported: the two cells' shares of the rule's roofline are
  on one scale.

NOT counted: the convolution (4 taps a channel), norms, gates, softplus,
the decay's one multiplication a state element, the sort and gathers of the
dispatch, a layer run again under the checkpoint, and whatever a chunked
form of the rule computes beyond the recurrence: that is the form's
overhead, so no reading of ``kda_neg.scan_roofline`` passes 100.
"""

from __future__ import annotations

from typing import Dict, List

# the KDA mixer's matrices, an expert's, and the bytes and ceiling of ONE
# layer's rule are counted by the module that counted them first: the two
# cells' shares of the rule's roofline stand on one scale by construction
from benchmark.flops_kimi_linear import (  # noqa: F401 — this module's too
    expert_params, kda_inner, kda_params, kda_pass_bytes,
    kda_scan_ceiling_pct)

__all__ = ["train_flops_per_token", "total_params", "flash_step_flops",
           "flash_step_bytes", "experts_step_flops", "experts_step_bytes",
           "kda_step_flops", "kda_step_bytes"]


def mixers(conf: Dict) -> List[str]:
    """The mixers of the layers that are run, in order."""
    return ["attention" if i in conf["gqa_layers"] else "kda"
            for i in range(conf["num_hidden_layers"])]


def kda_layers(conf: Dict) -> int:
    return mixers(conf).count("kda")


def softmax_layers(conf: Dict) -> int:
    return mixers(conf).count("attention")


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else conf["n_routed_experts"]


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_tok"] * conf["n_routed_experts"]
            / published_experts(conf))


def softmax_params(conf: Dict) -> int:
    """The five matrices of a softmax mixer: q, the output gate and the
    output projection over the query heads, k and v over the KV heads."""
    d, dh = conf["hidden_size"], conf["head_dim"]
    return d * dh * (3 * conf["num_attention_heads"]
                     + 2 * conf["num_key_value_heads"])


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    experts = conf["num_hidden_layers"] * (
        d * published_experts(conf)
        + (conf["n_shared_experts"] + held_per_token(conf))
        * expert_params(conf))
    return (kda_layers(conf) * kda_params(conf)
            + softmax_layers(conf) * softmax_params(conf) + experts
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the chip's train state holds: the held experts, the
    embedding, every norm, the convolutions, ``A_log`` a head and
    ``dt_bias`` a key channel, the selection biases."""
    d, linear = conf["hidden_size"], conf["linear_attn_config"]
    inner = kda_inner(conf)
    kda = (kda_params(conf) + d + linear["short_conv_kernel_size"] * 3 * inner
           + inner + linear["num_heads"] + linear["head_dim"])
    routed = published_experts(conf)
    expert = (d * routed + routed + d
              + (conf["n_shared_experts"] + conf["n_routed_experts"])
              * expert_params(conf))
    return (kda_layers(conf) * kda
            + softmax_layers(conf) * (softmax_params(conf) + d)
            + conf["num_hidden_layers"] * expert
            + 2 * d * conf["vocab_size"] + d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the
    softmax layers that are run (``flops.py`` has the derivation)."""
    return (6.0 * softmax_layers(conf) * seq * conf["num_attention_heads"]
            * conf["head_dim"])


def kda_flops_per_token(conf: Dict) -> float:
    """The rule's recurrence, forward and backward, per token, in the KDA
    layers that are run: 6 x head_dim^2 a token and head forward (``S'^T
    k``, the rank-one update, ``S^T q``: 2 operations a state element
    each), the backward pass twice that; ``flops_kimi_linear.py``'s count."""
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
    tokens, forward and backward, in the softmax layers that are run."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic that attention needs (``flops.py``'s count): forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv.  k and v are the KV heads' (an eighth of q's at 64 / 8)."""
    tokens, dh = rows * seq, conf["head_dim"]
    q = tokens * conf["num_attention_heads"] * dh * itemsize
    kv = tokens * conf["num_key_value_heads"] * dh * itemsize
    return float(softmax_layers(conf) * 3 * (2 * q + 2 * kv))


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every layer: each
    HELD row forward, the gradient to it and the gradient to its expert's
    weights."""
    return (6.0 * rows * seq * conf["num_hidden_layers"]
            * held_per_token(conf) * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_moe.py``'s count, over the experts and rows that are here)."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 3 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["n_routed_experts"] * expert_params(conf) \
        * itemsize
    return float(conf["num_hidden_layers"] * (row_bytes + weight_bytes))


def kda_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the rules of one train step need."""
    return kda_flops_per_token(conf) * rows * seq


def kda_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the rules of one train step need, all KDA layers
    (``kda_pass_bytes``).  Not counted: anything a chunked form writes and
    reads back (the chunk's matrices, its new values, the entering states,
    a level's scaled operands)."""
    return float(kda_layers(conf) * sum(
        kda_pass_bytes(conf, rows, seq, itemsize)))

