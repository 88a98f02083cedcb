"""Operations and bytes ONE CHIP'S SHARE of a Laguna model needs, from
shapes alone: what ``"flops": "flops_laguna"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``flash.window_roofline`` and ``moe.experts_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of three per-layer
lists: ``layer_types`` (``sliding_attention``: softmax attention under
``sliding_window``, query i sees key j iff ``0 <= i - j < window``;
``full_attention``: causal), ``num_attention_heads_per_layer`` (the QUERY
heads of that layer: one count a kind, 48 full and 64 sliding as published;
the KV heads and the head size are the model's) and ``mlp_layer_types``
(``dense``: a SwiGLU of ``intermediate_size``; ``sparse``: experts beside a
shared expert of ``shared_expert_intermediate_size``).

Counted is what THIS chip's forward and backward passes REQUIRE of the model
the file describes (``num_experts`` the experts held here, ``reduced`` their
published count; ``vocab_size`` the slice), attention BY KIND:

- 6 a matmul parameter and token: a mixer's projections AT ITS KIND'S HEAD
  COUNT (q and o ``d x H x d_head`` each, k and v at the KV heads', the gate
  ``d x H``: one number a head), the dense FFN where a layer has one, in an
  expert layer the router over ALL the published experts, the shared expert
  and the HELD rows in expectation — ``num_experts_per_tok x held /
  published`` experts a token (``moe.held_rows_share`` reports what a run
  had) —, the untied head over the slice (the embedding is a lookup);
- attention by the (q, k) PAIRS a layer's mask leaves, 12 x d_head a pair
  and QUERY HEAD OF THAT LAYER (``flops_afmoe.py`` has the count): ``seq (seq
  + 1) / 2`` pairs under 48 heads in a full layer, the window's pairs under
  64 in a sliding one — 8.26 M of 134.2 M at 16384 under 512.  NEVER one
  head count for both kinds (the file's ``num_attention_heads`` is the full
  layers'): the sliding layers' roofline would read a quarter low; never
  the causal pairs under a window: it would read sixteen times high.

NOT counted: norms, RoPE (half a head in the full layers), sigmoids, the
gate's product, SwiGLU's product, the sort and gathers of the dispatch, the
rows of the static row buffer that name an absent expert, a layer run again
under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.flops import head_dim
from benchmark.flops_afmoe import (  # noqa: F401 — this module's answers too
    causal_pairs, expert_params, held_per_token, published_experts,
    window_pairs)

FULL, SLIDING = "full_attention", "sliding_attention"


def _run(conf: Dict, key: str) -> List:
    """A per-layer list's entries for the layers that are run."""
    return list(conf[key][:conf["num_hidden_layers"]])


def heads(conf: Dict, kind: str) -> int:
    """The query heads of a layer of ``kind``: the ONE count the file gives
    that kind's layers (0 where the model has none of them)."""
    counts = {h for h, k in zip(_run(conf, "num_attention_heads_per_layer"),
                                _run(conf, "layer_types")) if k == kind}
    if len(counts) > 1:
        raise ValueError(f"{kind}: one head count a kind, not {counts}")
    return counts.pop() if counts else 0


def layers_of(conf: Dict, kind: str) -> int:
    return _run(conf, "layer_types").count(kind)


def windowed_layers(conf: Dict) -> int:
    return layers_of(conf, SLIDING)


def full_layers(conf: Dict) -> int:
    return layers_of(conf, FULL)


def expert_layers(conf: Dict) -> int:
    return _run(conf, "mlp_layer_types").count("sparse")


def dense_layers(conf: Dict) -> int:
    return _run(conf, "mlp_layer_types").count("dense")


def attention_params(conf: Dict, kind: str) -> int:
    """q and o at the kind's heads x d_head, k and v at the KV heads', the
    gate one column a head."""
    d, dh, h = conf["hidden_size"], head_dim(conf), heads(conf, kind)
    return 2 * d * h * dh + 2 * d * conf["num_key_value_heads"] * dh + d * h


def mixer_params(conf: Dict) -> int:
    """Every layer's mixer, each at its kind's head count."""
    return sum(layers_of(conf, kind) * attention_params(conf, kind)
               for kind in (FULL, SLIDING) if layers_of(conf, kind))


def dense_params(conf: Dict) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def shared_params(conf: Dict) -> int:
    return 3 * conf["hidden_size"] * conf["shared_expert_intermediate_size"]


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    experts = expert_layers(conf) * (
        d * published_experts(conf) + shared_params(conf)
        + held_per_token(conf) * expert_params(conf))
    return (mixer_params(conf) + dense_layers(conf) * dense_params(conf)
            + experts + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held and
    the shared experts, the selection biases, embedding and head, two norms
    a layer, the last norm."""
    d, routed = conf["hidden_size"], published_experts(conf)
    expert = (d * routed + routed + shared_params(conf)
              + conf["num_experts"] * expert_params(conf))
    return (mixer_params(conf) + dense_layers(conf) * dense_params(conf)
            + expert_layers(conf) * expert
            + conf["num_hidden_layers"] * 2 * d
            + 2 * d * conf["vocab_size"] + d)


def _pair_flops(conf: Dict, kind: str) -> float:
    """Forward and backward, a (q, k) pair of every head of one layer of
    ``kind``."""
    return 12.0 * heads(conf, kind) * head_dim(conf)


def window_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the SLIDING layers' attention needs in one train step of
    ``rows`` x ``seq`` tokens, forward and backward: the window's pairs
    under the sliding layers' heads."""
    return (_pair_flops(conf, SLIDING) * windowed_layers(conf) * rows
            * window_pairs(conf, seq))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What attention needs in one train step, every layer: the window's
    pairs under a sliding layer's heads, the causal ones under a full
    layer's."""
    return (window_step_flops(conf, rows, seq)
            + _pair_flops(conf, FULL) * full_layers(conf) * rows
            * causal_pairs(seq))


def _layer_bytes(conf: Dict, kind: str, rows: int, seq: int,
                 itemsize: int) -> float:
    """HBM traffic one layer's attention needs (``flops.py``'s count:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv), q-like arrays at the kind's heads.  A window takes
    nothing off it: every row of every operand is still read."""
    dh = head_dim(conf)
    q_like = rows * seq * heads(conf, kind) * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return 6.0 * q_like + 6.0 * kv_like


def window_step_bytes(conf: Dict, rows: int, seq: int,
                      itemsize: int = 2) -> float:
    return windowed_layers(conf) * _layer_bytes(conf, SLIDING, rows, seq,
                                                itemsize)


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    return (window_step_bytes(conf, rows, seq, itemsize)
            + full_layers(conf) * _layer_bytes(conf, FULL, rows, seq,
                                               itemsize))


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
