"""Operations and bytes a hybrid of gated delta-rule and softmax-attention
layers needs, from shapes alone: what ``"flops": "flops_olmo_hybrid"`` in a
configuration file names, the yardstick of its ``train_step.mfu_pct``,
``flash_roofline`` and ``gdn.scan_roofline``.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types`` (a
configuration file that cuts the depth keeps the published list), and a
layer holds softmax attention only where its entry is ``full_attention``:
``flops.py``'s ``attention_layers`` is NOT used here — it counts the whole
list, and it asks whether ``"attention"`` is in the name, which
``linear_attention`` passes.

Counted is what forward and backward REQUIRE: 6 a matmul parameter and
token (the untied head; the embedding is a lookup), causal attention in the
full layers, and the delta rule's RECURRENCE in the linear layers (below).
NOT counted: the convolution (4 taps a channel: elementwise), norms, gates,
softplus; a layer run again under the checkpoint; whatever a chunked form
of the rule computes beyond the recurrence (the chunk's ``k k^T``, its
triangular inverse, the products that form the chunk's new values) — that
is the form's overhead, so no reading of ``gdn.scan_roofline`` passes 100.

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
    return sum(kind == "full_attention" for kind in kinds(conf))


def linear_layers(conf: Dict) -> int:
    return sum(kind == "linear_attention" for kind in kinds(conf))


def key_inner(conf: Dict) -> int:
    return conf["linear_num_key_heads"] * conf["linear_key_head_dim"]


def value_inner(conf: Dict) -> int:
    return conf["linear_num_value_heads"] * conf["linear_value_head_dim"]


def matmul_params(conf: Dict) -> int:
    """Parameters that multiply an activation: q, k, v, o of a full layer;
    the input projection ([q | k | v | gate | a | b]) and the output
    projection of a linear layer; the three SwiGLU matrices of every
    layer; the untied head."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    keys, values = key_inner(conf), value_inner(conf)
    in_width = 2 * keys + 2 * values + 2 * conf["linear_num_value_heads"]
    return (attention_layers(conf) * (d * q + 2 * d * kv + q * d)
            + linear_layers(conf) * (d * in_width + values * d)
            + len(kinds(conf)) * 3 * d * conf["intermediate_size"]
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matmuls', the embedding,
    a linear layer's convolution (no bias), its ``A_log`` and ``dt_bias`` a
    head, its block norm and the gated norm's one weight of a head's value
    size; a full layer's block norm and the norms over its whole q and k
    projections; every layer's MLP norm; the last norm."""
    d, dh = conf["hidden_size"], head_dim(conf)
    heads = conf["linear_num_value_heads"]
    conv = conf["linear_conv_kernel_dim"] * (
        2 * key_inner(conf) + value_inner(conf))
    linear = conv + 2 * heads + d + conf["linear_value_head_dim"]
    full = d + (conf["num_attention_heads"]
                + conf["num_key_value_heads"]) * dh
    return (matmul_params(conf) + conf["vocab_size"] * d
            + linear_layers(conf) * linear + attention_layers(conf) * full
            + len(kinds(conf)) * d + d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the full
    layers that are run (``flops.py`` has the derivation)."""
    return (6.0 * attention_layers(conf) * seq
            * conf["num_attention_heads"] * head_dim(conf))


def gdn_flops_per_token(conf: Dict) -> float:
    """The gated delta rule's recurrence, forward and backward, per token,
    in the linear layers that are run.  A token and head, forward, with a
    state of value size x key size: ``S k`` (2 operations a state element),
    the rank-one update ``S - beta (S k) k^T + beta v k^T`` as one (2) and
    ``S q`` (2): 6 x key size x value size; the backward pass twice that.
    The decay's one multiplication a state element is left out."""
    state = conf["linear_key_head_dim"] * conf["linear_value_head_dim"]
    return (18.0 * linear_layers(conf) * conf["linear_num_value_heads"]
            * state)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token."""
    return (6.0 * matmul_params(conf) + attention_flops_per_token(conf, seq)
            + gdn_flops_per_token(conf))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x ``seq``
    tokens, forward and backward, in the full layers that are run."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the attention of one train step needs (``flops.py``'s
    count: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv; k and v at the KV heads the model has), in
    the full layers that are run."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return float(attention_layers(conf) * (6 * q_like + 6 * kv_like))


def gdn_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the delta rules of one train step need."""
    return gdn_flops_per_token(conf) * rows * seq


def gdn_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the delta rules of one train step need, all linear
    layers: forward reads q, k, v, the log-decay and beta and writes o;
    backward reads q, k, v, the two gates and o's gradient and writes the
    gradients of q, k, v and the two gates.  q and k are (tokens, heads x
    key size), v and o (tokens, heads x value size), the two gates (tokens,
    heads) in float32.  Not counted: anything a chunked form writes and
    reads back (the chunk's matrices, its new values, the entering
    states)."""
    tokens = rows * seq
    qk = 2 * tokens * key_inner(conf) * itemsize
    v = tokens * value_inner(conf) * itemsize
    gates = 2 * tokens * conf["linear_num_value_heads"] * 4
    forward = qk + 2 * v + gates
    backward = 2 * qk + 3 * v + 2 * gates
    return float(linear_layers(conf) * (forward + backward))
