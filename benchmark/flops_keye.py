"""Operations and bytes ONE CHIP'S SHARE of Keye-VL-2.0-30B-A3B's language
model needs, from shapes alone: what ``"flops": "flops_keye"`` in a
configuration file names, the yardstick of its ``train_step.mfu_pct``,
``flash_roofline``, ``dsa.attend_roofline``, ``dsa.index_roofline`` and
``moe.experts_roofline``.

Every layer is softmax attention over the keys a learned indexer picks
(``sa_config``: ``topk`` keys a query) and an expert layer without a shared
expert.  Counted is what THIS chip's forward and backward passes REQUIRE of
the model the file describes (``num_local_experts`` the experts held here,
``num_experts`` the router's outputs; ``vocab_size`` the slice), WHATEVER
implements it:

- 6 a matmul parameter and token: attention's four projections, the
  indexer's three, the router over ALL the published experts, the HELD rows
  in expectation (``num_experts_per_tok x held / published`` experts a
  token: 1 of the 8 at 16 of 128), the untied head over the slice;
- attention by the SELECTED (q, k) pairs, 12 x d_head a pair and head
  (``flops_afmoe.py``'s count): a query reads ``min(t + 1, topk)`` keys,
  31.46 M of the 134.2 M causal pairs at 16384 under 2048.  NEVER the causal
  pairs: a kernel that computes them all and masks does work the algorithm
  does not ask for, and one that skips dead tiles gains;
- the index scores over ALL causal pairs forward (a pair's score must exist
  before it can be left out: 2 x heads x size a pair), and the two gradient
  products (to the queries, to the key) over the selected pairs alone, where
  the indexer's loss has a gradient.

NOT counted: norms, RoPE, the ReLU and the heads' weighted sum, the top-k
itself (comparisons, not FLOPs), the heads' probabilities remade for the
indexer's target (a fused backward pass has them already), the router's
softmax, SwiGLU's product, the dispatch, a layer run again under the
checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops import head_dim
from benchmark.flops_afmoe import causal_pairs, expert_params

HELD = "num_local_experts"     # the experts this chip holds
ROUTED = "num_experts"         # the router's outputs: all the published


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return conf["num_experts_per_tok"] * conf[HELD] / conf[ROUTED]


def attention_params(conf: Dict) -> int:
    """q and o at heads x d_head, k and v at the KV heads'."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return 2 * d * q + 2 * d * kv


def indexer_params(conf: Dict) -> int:
    """The index heads' queries, the one key, a weight a head."""
    group = conf["sa_config"]
    heads, size = group["indexer_num_heads"], group["indexer_head_dim"]
    return conf["hidden_size"] * (heads * size + size + heads)


def selected_pairs(conf: Dict, seq: int) -> int:
    """(q, k) pairs the selection holds, a sequence: query ``t`` reads
    ``min(t + 1, topk)`` keys."""
    topk = min(conf["sa_config"]["topk"], seq)
    return topk * (topk + 1) // 2 + (seq - topk) * topk


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    layer = (attention_params(conf) + indexer_params(conf) + d * conf[ROUTED]
             + held_per_token(conf) * expert_params(conf))
    return conf["num_hidden_layers"] * layer + d * conf["vocab_size"]


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, embedding and head, two norms a layer, a head-sized norm each
    for q and k, the indexer's LayerNorm (weight and bias), the last norm."""
    d, dh = conf["hidden_size"], head_dim(conf)
    layer = (attention_params(conf) + 2 * dh + indexer_params(conf)
             + 2 * conf["sa_config"]["indexer_head_dim"] + d * conf[ROUTED]
             + conf[HELD] * expert_params(conf) + 2 * d)
    return conf["num_hidden_layers"] * layer + 2 * d * conf["vocab_size"] + d


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the attention over the SELECTED pairs needs in one train step,
    every layer, forward and backward."""
    return (12.0 * conf["num_attention_heads"] * head_dim(conf)
            * conf["num_hidden_layers"] * rows * selected_pairs(conf, seq))


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic that attention needs (``flops.py``'s count: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv; k and v at the KV heads).  The selection takes nothing off
    it: every row of every operand is read by some query."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return conf["num_hidden_layers"] * (6.0 * q_like + 6.0 * kv_like)


def index_pair_flops(conf: Dict, rows: int, seq: int) -> float:
    """The index-score products of one train step, every layer: forward
    over ALL causal pairs, the two gradient products over the selected."""
    group = conf["sa_config"]
    pair = 2.0 * group["indexer_num_heads"] * group["indexer_head_dim"]
    return conf["num_hidden_layers"] * rows * pair * (
        causal_pairs(seq) + 2 * selected_pairs(conf, seq))


def index_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the scope ``dsa_index`` needs in one train step: the indexer's
    three projections (6 a parameter and token) and the score products."""
    return (6.0 * conf["num_hidden_layers"] * indexer_params(conf)
            * rows * seq + index_pair_flops(conf, rows, seq))


def index_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the indexer needs: its projections' matrices three times
    (forward, both gradients), the normed input read and the operands
    written forward, read again with their gradients written backward.  The
    ``(seq, seq)`` scores are not in it: a fused form never writes them."""
    group = conf["sa_config"]
    width = (group["indexer_num_heads"] + 1) * group["indexer_head_dim"] \
        + group["indexer_num_heads"]
    tokens = rows * seq
    return conf["num_hidden_layers"] * itemsize * (
        3.0 * indexer_params(conf)
        + 2.0 * tokens * conf["hidden_size"] + 4.0 * tokens * width)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + (flash_step_flops(conf, 1, seq)
               + index_pair_flops(conf, 1, seq)) / seq)


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
    weight_bytes = 3 * conf[HELD] * expert_params(conf) * itemsize
    return float(conf["num_hidden_layers"] * (row_bytes + weight_bytes))
