"""Operations and bytes ONE CHIP'S SHARE of an SDAR model needs when it is
TRAINED BY BLOCK DIFFUSION, from shapes alone: what ``"flops":
"flops_sdar"`` in a configuration file names, the yardstick of its
``train_step.mfu_pct``, ``flash_roofline``, ``bd.attend_roofline`` and
``moe.experts_roofline``.

A step runs ONE pass over ``[noised ; clean]``: a sequence of ``seq`` DATA
tokens is ``2 seq`` rows through every layer and ``seq`` rows (the noised
half) through the head.  Every count here is per data token or per ``rows x
seq`` data tokens, as the train loop counts them (``tokens`` of a step are
its data tokens: what a user of such a job counts), so the two rows a token
are INSIDE the counts:

- 6 a matmul parameter and ROW: attention's four projections, the router
  over ALL the published experts and the HELD rows in expectation
  (``num_experts_per_tok x held / published`` experts a row) — TWO rows a
  token; the untied head over the slice — ONE row a token;
- attention by the (q, k) pairs the block rule NEEDS, 12 x d_head a pair and
  head (``flops_afmoe.py``'s count: 4 forward, 8 backward): a row of either
  stream reads ``(r // B + 1) B`` keys — the clean row its own block and the
  earlier ones, the noised row the earlier clean blocks and its own noised
  block —, ``seq (seq + B)`` pairs together (67.1 M at 8192 under 4).  NEVER
  the causal square over the 2 seq rows (134.2 M): a kernel that walks it
  and masks does work the rule does not ask for.

NOT counted: norms, RoPE, the noise draws, the router's softmax, SwiGLU's
product, the dispatch, a layer run again under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names; ``num_experts`` the experts held here, ``reduced``
their published count; ``vocab_size`` the slice).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops import head_dim
from benchmark.flops_afmoe import (  # noqa: F401 — this module's answers too
    expert_params, held_per_token, published_experts)
from benchmark.flops_mellum import attention_params

STREAMS = 2     # rows a data token is, through every layer


def block_length(conf: Dict) -> int:
    return dict(conf["block_diffusion"])["block_length"]


def needed_pairs(conf: Dict, seq: int) -> int:
    """(q, k) pairs a head and layer needs of one sequence, both streams."""
    return seq * (seq + block_length(conf))


def layer_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one ROW's activation in a layer here."""
    return (attention_params(conf)
            + conf["hidden_size"] * published_experts(conf)
            + held_per_token(conf) * expert_params(conf))


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, embedding and head, two norms a layer, a head-sized norm each
    for q and k, the last norm."""
    d = conf["hidden_size"]
    layer = (attention_params(conf) + 2 * head_dim(conf)
             + d * published_experts(conf)
             + conf["num_experts"] * expert_params(conf) + 2 * d)
    return conf["num_hidden_layers"] * layer + 2 * d * conf["vocab_size"] + d


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the attention over the NEEDED pairs takes in one train step,
    every layer, forward and backward."""
    return (12.0 * conf["num_attention_heads"] * head_dim(conf)
            * conf["num_hidden_layers"] * rows * needed_pairs(conf, seq))


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic that attention needs (``flops.py``'s count over the 2 seq
    rows: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv; k and v at the KV heads)."""
    dh, both = head_dim(conf), STREAMS * seq
    q_like = rows * both * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * both * conf["num_key_value_heads"] * dh * itemsize
    return conf["num_hidden_layers"] * (6.0 * q_like + 6.0 * kv_like)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one DATA token of training on this chip."""
    return (6.0 * (STREAMS * conf["num_hidden_layers"]
                   * layer_matmul_params(conf)
                   + conf["hidden_size"] * conf["vocab_size"])
            + flash_step_flops(conf, 1, seq) / seq)


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every layer: each
    HELD row (of the 2 seq a sequence) forward, the gradient to it and the
    gradient to its expert's weights."""
    return (6.0 * rows * STREAMS * seq * conf["num_hidden_layers"]
            * held_per_token(conf) * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_moe.py``'s count, over the experts and rows that are here)."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * STREAMS * seq * held_per_token(conf)
    row_bytes = 3 * 3 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["num_experts"] * expert_params(conf) * itemsize
    return float(conf["num_hidden_layers"] * (row_bytes + weight_bytes))
