"""Operations and bytes a hybrid of Mamba-2 and attention layers needs,
from shapes alone: what ``"flops": "flops_hybrid"`` in a configuration
file names, the yardstick of its ``train_step.mfu_pct``,
``flash_roofline`` and ``ssm.scan_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types``
(a configuration file that cuts the depth keeps the published list, so
``flops.py``'s ``attention_layers``, which counts the whole list, is not
used here: it would count four attention layers where one is run).

Counted is what forward and backward REQUIRE: 6 a matmul parameter and
token (the tied table once, as the head's matmul; the embedding is a
lookup), causal attention in the attention layers, and the state-space
scan in the Mamba layers (below).  NOT counted: the convolution (4 taps a
channel: elementwise), norms, gates, softplus, ``D x``; a layer run again
under the checkpoint; whatever a chunked implementation computes above
the diagonal of a chunk or in float32.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.flops import head_dim


def kinds(conf: Dict) -> List[str]:
    """The mixers of the layers that are run, in order."""
    return list(conf["layer_types"][:conf["num_hidden_layers"]])


def attention_layers(conf: Dict) -> int:
    return sum(kind == "attention" for kind in kinds(conf))


def mamba_layers(conf: Dict) -> int:
    return sum(kind == "mamba" for kind in kinds(conf))


def mamba_inner(conf: Dict) -> int:
    return conf["mamba_n_heads"] * conf["mamba_d_head"]


def matmul_params(conf: Dict) -> int:
    """Parameters that multiply an activation: q, k, v, o of an attention
    layer; the input projection ([z | x B C | dt]) and the output
    projection of a Mamba layer; the three SwiGLU matrices of every
    layer; the tied table, once, as the head."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    inner = mamba_inner(conf)
    in_width = (2 * inner + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
                + conf["mamba_n_heads"])
    return (attention_layers(conf) * (d * q + 2 * d * kv + q * d)
            + mamba_layers(conf) * (d * in_width + inner * d)
            + len(kinds(conf)) * 3 * d * conf["shared_intermediate_size"]
            + d * conf["vocab_size"])


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the
    attention layers that are run (``flops.py`` has the derivation)."""
    return (6.0 * attention_layers(conf) * seq
            * conf["num_attention_heads"] * head_dim(conf))


def ssd_flops_per_token(conf: Dict) -> float:
    """The state-space scan, forward and backward (forward x 3), per
    token, in the Mamba layers that are run, as the chunked algorithm
    needs it with chunks of ``mamba_chunk_size`` tokens ``Q``: per chunk
    the CAUSAL HALF (``Q (Q + 1) / 2`` pairs) of ``C B^T`` (2 x d_state a
    pair and group) and of the masked matrix times ``dt x`` (2 x d_head a
    pair and head), the chunk's state ``B^T (dt x)`` and the entering
    state's output ``C H`` (each 2 x Q x d_state x heads x d_head).  The
    carry across chunks (a few operations a state element) is left out."""
    q, n = conf["mamba_chunk_size"], conf["mamba_d_state"]
    inner = mamba_inner(conf)
    pairs = q * (q + 1) // 2
    chunk = (2.0 * pairs * (conf["mamba_n_groups"] * n + inner)
             + 4.0 * q * n * inner)
    return 3.0 * mamba_layers(conf) * chunk / q


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token."""
    return (6.0 * matmul_params(conf) + attention_flops_per_token(conf, seq)
            + ssd_flops_per_token(conf))


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


def ssd_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the state-space scans of one train step need."""
    return ssd_flops_per_token(conf) * rows * seq


def ssd_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the state-space scans of one train step need, all Mamba
    layers: forward reads x, B, C, dt and writes y; backward reads x, B,
    C, dt and y's gradient and writes the gradients of x, B, C, dt.  x and
    y are (tokens, heads x d_head), B and C (tokens, groups x d_state), dt
    (tokens, heads) in float32.  Not counted: anything a chunked form
    writes and reads back (decay matrices, chunk states), A and D."""
    tokens = rows * seq
    x = tokens * mamba_inner(conf) * itemsize
    bc = 2 * tokens * conf["mamba_n_groups"] * conf["mamba_d_state"] * itemsize
    dt = tokens * conf["mamba_n_heads"] * 4
    forward = 2 * x + bc + dt
    backward = 3 * x + 2 * (bc + dt)
    return float(mamba_layers(conf) * (forward + backward))
