"""Operations and bytes a mixture-of-experts configuration needs, from
shapes alone: what ``"flops": "flops_moe"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct`` and
``moe.experts_roofline``, kept beside ``flops.py`` (whose dense count
takes ``intermediate_size`` for one FFN and so sees one expert of the
``num_experts_per_tok`` a token uses).  Attention is the dense
decoder's: its counts are ``flops.py``'s.

Counted is what forward and backward REQUIRE: every token meets
``num_experts_per_tok`` experts and the router.  Work the program adds —
rows computed twice where two groups share a row tile, the forward run
again under remat — is executed and not counted.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict

from benchmark import flops
from benchmark.flops import flash_step_bytes, flash_step_flops  # noqa: F401


def expert_params(conf: Dict) -> int:
    """The three SwiGLU matrices of ONE expert."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def active_matmul_params(conf: Dict) -> int:
    """Parameters that multiply one token's activation: q, k, v, o, the
    router and ``num_experts_per_tok`` experts in every layer, and the
    untied output head."""
    d, dh = conf["hidden_size"], flops.head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    per_layer = (d * q + 2 * d * kv + q * d + d * conf["num_experts"]
                 + conf["num_experts_per_tok"] * expert_params(conf))
    return conf["num_hidden_layers"] * per_layer + d * conf["vocab_size"]


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: all the experts, the
    embedding, the norms (two of the layer, two over q and k, the last)."""
    d, dh = conf["hidden_size"], flops.head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    per_layer = (d * q + 2 * d * kv + q * d + d * conf["num_experts"]
                 + conf["num_experts"] * expert_params(conf)
                 + 2 * d + q + kv)
    return (conf["num_hidden_layers"] * per_layer
            + 2 * d * conf["vocab_size"] + d)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token: 6 per active matmul parameter
    (2 forward, 4 backward) plus causal attention."""
    return (6.0 * active_matmul_params(conf)
            + flops.attention_flops_per_token(conf, seq))


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step of ``rows`` x
    ``seq`` tokens, all layers: each of a token's experts forward, the
    gradient to its rows and the gradient to its weights."""
    return (6.0 * rows * seq * conf["num_hidden_layers"]
            * conf["num_experts_per_tok"] * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need, all
    layers.  Each of the three products, in each of its three passes
    (forward, gradient to the rows, gradient to the weights), reads or
    writes every expert's matrix once and reads and writes its
    ``tokens x num_experts_per_tok`` rows once: forward reads the input
    rows and writes the output rows, the rows' gradient reads the output's
    gradient and writes the input's, the weights' gradient reads both."""
    d, m = conf["hidden_size"], conf["intermediate_size"]
    assignments = rows * seq * conf["num_experts_per_tok"]
    row_bytes = 3 * 3 * assignments * (d + m) * itemsize
    weight_bytes = 3 * conf["num_experts"] * expert_params(conf) * itemsize
    return float(conf["num_hidden_layers"] * (row_bytes + weight_bytes))
