"""Operations and bytes ONE CHIP'S SHARE of a Mellum 2 model needs, from
shapes alone: what ``"flops": "flops_mellum"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``flash.window_roofline`` and ``moe.experts_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types``:
``sliding_attention`` softmax attention under ``sliding_window`` (query i
sees key j iff ``0 <= i - j < window``), ``full_attention`` causal
attention; EVERY layer's FFN is the expert layer (``mlp_layer_types`` is
``sparse`` throughout), with no shared expert.

Counted is what THIS chip's forward and backward passes REQUIRE of the model
the file describes (``num_experts`` the experts held here, ``reduced`` their
published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: an attention mixer's FOUR projections (q,
  k, v, o), in every layer the router over ALL the published experts and
  the HELD rows in expectation — ``num_experts_per_tok x held / published``
  experts a token, 2 of the 8 at 16 of 64, which is what random weights and
  tokens give (``moe.held_rows_share`` reports what a run had) —, the
  untied head over the slice (the embedding is a lookup);
- attention by the (q, k) PAIRS a layer's mask leaves (``flops_afmoe.py``'s
  count, 12 x d_head a pair and head): ``seq (seq + 1) / 2`` in a full
  layer, in a windowed one the pairs inside the window — 16.25 M of the
  134.2 M causal ones at 16384 under 1024.  NEVER the causal pairs there: a
  count that held them would read the windowed kernels' roofline eight times
  too high.

NOT counted: norms, RoPE (two tables a step, a rotation a layer and pass),
the router's softmax, SwiGLU's product, the sort and gathers of the dispatch,
the rows of the static row buffer that name an absent expert, a layer run
again under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops import head_dim
from benchmark.flops_afmoe import (  # noqa: F401 — this module's answers too
    causal_pairs, expert_params, flash_step_bytes, flash_step_flops,
    full_layers, held_per_token, published_experts, window_pairs,
    window_step_bytes, window_step_flops, windowed_layers)


def attention_params(conf: Dict) -> int:
    """q and o at heads x d_head, k and v at the KV heads'."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return 2 * d * q + 2 * d * kv


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    layer = (attention_params(conf) + d * published_experts(conf)
             + held_per_token(conf) * expert_params(conf))
    return conf["num_hidden_layers"] * layer + d * conf["vocab_size"]


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, embedding and head, two norms a layer, the last norm."""
    d = conf["hidden_size"]
    layer = (attention_params(conf) + d * published_experts(conf)
             + conf["num_experts"] * expert_params(conf) + 2 * d)
    return conf["num_hidden_layers"] * layer + 2 * d * conf["vocab_size"] + d


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + flash_step_flops(conf, 1, seq) / seq)


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every layer: each
    HELD row forward, the gradient to it and the gradient to its expert's
    weights."""
    return (6.0 * rows * seq * conf["num_hidden_layers"]
            * held_per_token(conf) * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_moe.py``'s count, over the experts and rows that are here):
    each of the three products, in each of its three passes, reads or
    writes every held expert's matrix once and reads and writes the held
    rows once."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 3 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["num_experts"] * expert_params(conf) * itemsize
    return float(conf["num_hidden_layers"] * (row_bytes + weight_bytes))
