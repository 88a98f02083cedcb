"""Operations and bytes ONE CHIP'S SHARE of an AFMoE model (Trinity) needs,
from shapes alone: what ``"flops": "flops_afmoe"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``flash.window_roofline`` and ``moe.experts_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types``:
``sliding_attention`` softmax attention under ``sliding_window`` (query i
sees key j iff ``0 <= i - j < window``), ``full_attention`` causal
attention; the first ``num_dense_layers`` layers have a dense FFN, the later
ones experts beside a shared expert.

Counted is what THIS chip's forward and backward passes REQUIRE of the model
the file describes (``num_experts`` the experts held here, ``reduced`` their
published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: an attention mixer's FIVE projections (q,
  k, v, the output gate, o), the dense FFN of the leading layers, in every
  later layer the router over ALL the published experts, the shared expert
  and the HELD rows in expectation — ``num_experts_per_tok x held /
  published`` experts a token, which is what random weights and tokens give
  (``moe.held_rows_share`` reports what a run had) —, the untied head over
  the slice (the embedding is a lookup);
- attention by the (q, k) PAIRS a layer's mask leaves, 12 x d_head a pair
  and head (forward the scores and the output, 2 products of 2 x d_head;
  backward four more): ``seq (seq + 1) / 2`` pairs in a full layer, in a
  windowed one the pairs inside the window — 25.17 M of 33.56 M at 8192
  under 4096.  NEVER the causal pairs there: the windowed kernels skip what
  lies before the window, and a count that held it would read their
  roofline, ``flash_roofline`` and ``train_step.mfu_pct`` a third too high
  in those layers.

NOT counted: norms (four a layer, two a head), RoPE, sigmoids, the output
gate's product, SwiGLU's product, the sort and gathers of the dispatch, the
rows of the static row buffer that name an absent expert, a layer run again
under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.flops import head_dim


def mixers(conf: Dict) -> List[str]:
    """The mixers of the layers that are run, in order."""
    return list(conf["layer_types"][:conf["num_hidden_layers"]])


def windowed_layers(conf: Dict) -> int:
    return sum(m == "sliding_attention" for m in mixers(conf))


def full_layers(conf: Dict) -> int:
    return sum(m == "full_attention" for m in mixers(conf))


def expert_layers(conf: Dict) -> int:
    return conf["num_hidden_layers"] - conf["num_dense_layers"]


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("num_experts")
    return cut["published"] if cut else conf["num_experts"]


def attention_params(conf: Dict) -> int:
    """q, the output gate and o at heads x d_head, k and v at the KV
    heads'."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return 3 * d * q + 2 * d * kv


def expert_params(conf: Dict) -> int:
    """The three SwiGLU matrices of ONE routed expert."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_tok"] * conf["num_experts"]
            / published_experts(conf))


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    dense = conf["num_dense_layers"] * 3 * d * conf["intermediate_size"]
    experts = expert_layers(conf) * (
        d * published_experts(conf)
        + (conf["num_shared_experts"] + held_per_token(conf))
        * expert_params(conf))
    return (conf["num_hidden_layers"] * attention_params(conf) + dense
            + experts + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held and
    the shared experts, embedding and head, four norms a layer and two a
    head, the selection biases, the last norm."""
    d, dh = conf["hidden_size"], head_dim(conf)
    routed = published_experts(conf)
    attention = attention_params(conf) + 2 * d + 2 * dh
    dense = 3 * d * conf["intermediate_size"] + 2 * d
    expert = (d * routed + routed + 2 * d
              + (conf["num_experts"] + conf["num_shared_experts"])
              * expert_params(conf))
    return (conf["num_hidden_layers"] * attention
            + conf["num_dense_layers"] * dense
            + expert_layers(conf) * expert + 2 * d * conf["vocab_size"] + d)


def causal_pairs(seq: int) -> int:
    """(q, k) pairs a full layer's mask leaves, a sequence and head."""
    return seq * (seq + 1) // 2


def window_pairs(conf: Dict, seq: int) -> int:
    """... a windowed layer's: row r sees ``min(r + 1, window)`` keys."""
    w = min(conf["sliding_window"], seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _pair_flops(conf: Dict) -> float:
    """Forward and backward, a (q, k) pair of every head of one layer."""
    return 12.0 * conf["num_attention_heads"] * head_dim(conf)


def window_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the WINDOWED layers' attention needs in one train step of
    ``rows`` x ``seq`` tokens, forward and backward: the window's pairs."""
    return (_pair_flops(conf) * windowed_layers(conf) * rows
            * window_pairs(conf, seq))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What attention needs in one train step, every layer: the window's
    pairs in a windowed layer, the causal ones in a full one."""
    return (window_step_flops(conf, rows, seq)
            + _pair_flops(conf) * full_layers(conf) * rows
            * causal_pairs(seq))


def _layer_bytes(conf: Dict, rows: int, seq: int, itemsize: int) -> float:
    """HBM traffic one layer's attention needs (``flops.py``'s count:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv; k and v at the KV heads the model has).  A window
    takes nothing off it: every row of every operand is still read."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return 6.0 * q_like + 6.0 * kv_like


def window_step_bytes(conf: Dict, rows: int, seq: int,
                      itemsize: int = 2) -> float:
    return windowed_layers(conf) * _layer_bytes(conf, rows, seq, itemsize)


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    return conf["num_hidden_layers"] * _layer_bytes(conf, rows, seq,
                                                    itemsize)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + flash_step_flops(conf, 1, seq) / seq)


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every expert
    layer: each HELD row forward, the gradient to it and the gradient to
    its expert's weights."""
    return (6.0 * rows * seq * expert_layers(conf) * held_per_token(conf)
            * expert_params(conf))


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
    return float(expert_layers(conf) * (row_bytes + weight_bytes))
