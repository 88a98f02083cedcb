"""Operations and bytes ONE CHIP'S SHARE of an LFM2-MoE model needs, from
shapes alone: what ``"flops": "flops_lfm2"`` in a configuration file names,
the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``sconv.gate_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types`` (a
file that cuts the depth keeps the published list): ``conv`` a gated
short-convolution mixer, ``full_attention`` softmax attention; the first
``num_dense_layers`` layers have a dense FFN, the later ones experts.
``flops.py``'s ``attention_layers`` is NOT used: it counts the whole list.

Counted is what THIS chip's forward and backward passes REQUIRE of the model
the file describes (``num_experts`` the experts held here, ``reduced``
their published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: a conv mixer's two projections (d -> 3 d
  and d -> d), an attention mixer's four, the dense FFN of the leading
  layers, in every later layer the router over ALL the published experts
  and the HELD rows in expectation — ``num_experts_per_tok x held /
  published`` experts a token, which is what random weights and tokens give
  (``moe.held_rows_share`` reports what a run had) —, the tied head over the
  slice (the embedding is a lookup);
- causal attention in the attention layers (``flops.py`` has the
  derivation).

NOT counted: the short convolution and its two gates (elementwise: 22
operations a channel and token, a ten-thousandth of the rest;
``sconv_step_flops`` has them for the op's own roofline), norms, RoPE,
sigmoids, SwiGLU's product, the sort and gathers of the dispatch, the rows
of the static row buffer that name an absent expert, a layer run again under
the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.flops import head_dim


def ops(conf: Dict) -> List[str]:
    """The mixers of the layers that are run, in order."""
    return list(conf["layer_types"][:conf["num_hidden_layers"]])


def attention_layers(conf: Dict) -> int:
    return sum(op == "full_attention" for op in ops(conf))


def conv_layers(conf: Dict) -> int:
    return sum(op == "conv" for op in ops(conf))


def expert_layers(conf: Dict) -> int:
    return conf["num_hidden_layers"] - conf["num_dense_layers"]


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("num_experts")
    return cut["published"] if cut else conf["num_experts"]


def conv_params(conf: Dict) -> int:
    """The two projections of a conv mixer: d -> [B | C | x], d -> d."""
    return 4 * conf["hidden_size"] ** 2


def attention_params(conf: Dict) -> int:
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d


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
        + held_per_token(conf) * expert_params(conf))
    return (conv_layers(conf) * conv_params(conf)
            + attention_layers(conf) * attention_params(conf)
            + dense + experts + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, the tied table, a conv mixer's taps, both per-head norms of
    an attention mixer, every block's norm, the selection biases, the
    last norm."""
    d, dh = conf["hidden_size"], head_dim(conf)
    routed = published_experts(conf)
    conv = conv_params(conf) + conf["conv_L_cache"] * d + d
    attention = attention_params(conf) + d + 2 * dh
    dense = 3 * d * conf["intermediate_size"] + d
    expert = (d * routed + routed + d
              + conf["num_experts"] * expert_params(conf))
    return (conv_layers(conf) * conv + attention_layers(conf) * attention
            + conf["num_dense_layers"] * dense
            + expert_layers(conf) * expert + d * conf["vocab_size"] + d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the
    attention layers that are run."""
    return (6.0 * attention_layers(conf) * seq
            * conf["num_attention_heads"] * head_dim(conf))


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x ``seq``
    tokens, forward and backward, in the attention layers that are run."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the attention of one train step needs (``flops.py``'s
    count: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv; k and v at the KV heads the model has), in
    the attention layers that are run."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return float(attention_layers(conf) * (6 * q_like + 6 * kv_like))


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


def sconv_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the gated short convolutions of one train step need, every
    conv layer, a channel and token: forward ``B * x`` (1), the taps'
    sum (``L`` products, ``L - 1`` additions), ``C *`` (1): 7 at the
    published 3 taps; backward ``dy * c`` and ``dy * C`` (2), the taps'
    sum of ``dc`` against time (``2 L - 1``), ``dz * x`` and ``dz * B``
    (2), a product and an addition a tap for the taps' gradient (``2
    L``): 15.  No MXU runs any of it."""
    taps = conf["conv_L_cache"]
    per = (2 * taps + 1) + (4 * taps + 3)
    return float(conv_layers(conf) * rows * seq * conf["hidden_size"] * per)


def sconv_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """The LEAST HBM traffic of the gated short convolutions of one train
    step, every conv layer: forward reads B, C, x and writes y (4 arrays
    of tokens x d); backward reads B, C, x and dy and writes dB, dC, dx
    (7): 11 x tokens x d x itemsize a layer, 369 MB at 8192 x 2048 in
    bfloat16.  Not counted: the taps and their gradient (``L x d``
    numbers), the forward pass run again under the layer checkpoint."""
    return float(conv_layers(conf) * 11 * rows * seq * conf["hidden_size"]
                 * itemsize)
