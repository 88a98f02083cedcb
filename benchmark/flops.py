"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick for ``train_step.mfu_pct`` and ``flash_roofline``: kept
here, beside the cells, so that no change to the program can move it.
Counts are what the forward and backward passes REQUIRE: work the
program repeats (rematerialised layers, the flash backward's recomputed
scores) is executed but not counted, so doing less of it shows as a gain.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).  Its ``"flops"`` names the module under
``benchmark/`` that counts for it — this one for a dense decoder — and
every reader counts through ``of(conf)``.  Such a module answers
``train_flops_per_token(conf, seq)``, ``flash_step_flops(conf, rows,
seq)`` and ``flash_step_bytes(conf, rows, seq)``, beside the counts of
its own kernels.
"""

from __future__ import annotations

import importlib
from typing import Dict


def of(conf: Dict):
    """The FLOP module a configuration file names."""
    return importlib.import_module("benchmark." + conf["flops"])


def counts_experts(conf: Dict) -> bool:
    """Whether that module counts an expert layer (``experts_step_flops``):
    a cell of such a configuration runs the ``moe_*`` scopes."""
    return hasattr(of(conf), "experts_step_flops")


def head_dim(conf: Dict) -> int:
    return conf.get("head_dim") or (
        conf["hidden_size"] // conf["num_attention_heads"])


def attention_layers(conf: Dict) -> int:
    """Layers that hold softmax attention: the entries of ``layer_types``
    that are attention (``attention``, ``full_attention``, ...) where the
    file has the key, else every layer."""
    if "layer_types" in conf:
        return sum("attention" in kind for kind in conf["layer_types"])
    return conf["num_hidden_layers"]


def matmul_params(conf: Dict) -> int:
    """Parameters that multiply an activation: q, k, v, o of every
    attention layer, the three SwiGLU matrices of every layer, and the
    untied output head.  The embedding is a lookup and the norms are
    elementwise: neither counts."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return (attention_layers(conf) * (d * q + 2 * d * kv + q * d)
            + conf["num_hidden_layers"] * 3 * d * conf["intermediate_size"]
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds (embedding and norms too)."""
    d = conf["hidden_size"]
    return (matmul_params(conf) + conf["vocab_size"] * d
            + (2 * conf["num_hidden_layers"] + 1) * d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token: forward
    is QK^T and PV (2 matmuls of 2*s*d_head per head and query, half of
    them under the causal mask), backward is dV, dP, dQ, dK (4 more):
    6 * attention layers * s * heads * d_head."""
    return (6.0 * attention_layers(conf) * seq
            * conf["num_attention_heads"] * head_dim(conf))


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token: 6 per matmul parameter (2
    forward, 4 backward) plus causal attention."""
    return 6.0 * matmul_params(conf) + attention_flops_per_token(conf, seq)


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x
    ``seq`` tokens, all attention layers, forward and backward."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the attention of one train step needs, all attention
    layers:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv.  k, v, dk, dv are counted at the KV heads the
    model has (a kernel that reads them repeated moves more than it
    needs); the float32 log-sum-exp row is 1/d_head of a tensor and is
    left out."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    forward = 2 * q_like + 2 * kv_like
    backward = 4 * q_like + 4 * kv_like
    return float(attention_layers(conf) * (forward + backward))


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> Dict:
    """The least time one chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, and which of the two it is."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
