"""Operations and bytes ONE CHIP'S SHARE of a Nemotron-H model needs, from
shapes alone: what ``"flops": "flops_nemotron_h"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``ssm.groups_scan_roofline``.

The model is the FIRST ``num_hidden_layers`` characters of
``hybrid_override_pattern`` (a file that cuts the depth keeps the published
string): ``M`` a Mamba-2 mixer, ``*`` softmax attention, ``E`` an expert
FFN, ``-`` a dense FFN — a layer is ONE of them.

Counted is what THIS chip's forward and backward passes REQUIRE of the model
the file describes (``n_routed_experts`` the experts held here, ``reduced``
their published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: a Mamba layer's input projection (d ->
  [z | x B C | dt]) and output projection, an attention layer's four, in an
  expert layer the router over ALL the published experts, the shared expert
  for every token and the HELD rows in expectation — ``num_experts_per_tok x
  held / published`` experts a token, which is what random weights and
  tokens give (``moe.held_rows_share`` reports what a run had) —, each
  expert TWO matrices (``relu2`` has no gate), the head over the slice (the
  embedding is a lookup);
- causal attention in the ``*`` layers (``flops.py`` has the derivation);
- the state-space scan in the ``M`` layers as the chunked algorithm needs
  it (``flops_hybrid.py``'s count, from this file's keys: B and C of
  ``n_groups`` groups, chunks of ``chunk_size``).

NOT counted: the convolution (4 taps a channel: elementwise), norms, gates,
softplus, ``D x``, sigmoids, the square, the sort and gathers of the
dispatch, the rows of the static row buffer that name an absent expert, a
layer run again under the checkpoint, whatever a chunked scan computes
above the diagonal of a chunk or in float32.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict


def layers(conf: Dict, kind: str) -> int:
    """Layers of ``kind`` (a character of the pattern) that are run."""
    return conf["hybrid_override_pattern"][:conf["num_hidden_layers"]].count(
        kind)


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else conf["n_routed_experts"]


def mamba_inner(conf: Dict) -> int:
    return conf["mamba_num_heads"] * conf["mamba_head_dim"]


def mamba_conv_dim(conf: Dict) -> int:
    """What the convolution runs over: x, B and C side by side."""
    return mamba_inner(conf) + 2 * conf["n_groups"] * conf["ssm_state_size"]


def mamba_params(conf: Dict) -> int:
    """The two projections of a Mamba layer."""
    d, inner = conf["hidden_size"], mamba_inner(conf)
    return (d * (inner + mamba_conv_dim(conf) + conf["mamba_num_heads"])
            + inner * d)


def attention_params(conf: Dict) -> int:
    d, dh = conf["hidden_size"], conf["head_dim"]
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d


def expert_params(conf: Dict) -> int:
    """The TWO matrices of ONE routed expert."""
    return 2 * conf["hidden_size"] * conf["moe_intermediate_size"]


def shared_params(conf: Dict) -> int:
    return (2 * conf["hidden_size"] * conf["n_shared_experts"]
            * conf["moe_shared_expert_intermediate_size"])


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_tok"] * conf["n_routed_experts"]
            / published_experts(conf))


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    experts = (d * published_experts(conf) + shared_params(conf)
               + held_per_token(conf) * expert_params(conf))
    return (layers(conf, "M") * mamba_params(conf)
            + layers(conf, "*") * attention_params(conf)
            + layers(conf, "E") * experts
            + layers(conf, "-") * 2 * d * conf["intermediate_size"]
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, both tables, a Mamba layer's taps with their bias, its three
    numbers a head and its gated norm's weight, every layer's norm, the
    selection biases, the last norm."""
    d, routed = conf["hidden_size"], published_experts(conf)
    mamba = (mamba_params(conf) + d
             + (conf["conv_kernel"] + 1) * mamba_conv_dim(conf)
             + 3 * conf["mamba_num_heads"] + mamba_inner(conf))
    expert = (d + d * routed + routed + shared_params(conf)
              + conf["n_routed_experts"] * expert_params(conf))
    return (layers(conf, "M") * mamba
            + layers(conf, "*") * (attention_params(conf) + d)
            + layers(conf, "E") * expert
            + layers(conf, "-") * (2 * d * conf["intermediate_size"] + d)
            + 2 * d * conf["vocab_size"] + d)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token, in the
    attention layers that are run."""
    return (6.0 * layers(conf, "*") * seq * conf["num_attention_heads"]
            * conf["head_dim"])


def ssd_flops_per_token(conf: Dict) -> float:
    """The state-space scan, forward and backward (forward x 3), per token,
    in the Mamba layers that are run, as the chunked algorithm needs it with
    chunks of ``chunk_size`` tokens ``Q``: per chunk the CAUSAL HALF (``Q (Q
    + 1) / 2`` pairs) of ``C B^T`` (2 x d_state a pair and GROUP) and of the
    masked matrix times ``dt x`` (2 x d_head a pair and head), the chunk's
    state ``B^T (dt x)`` and the entering state's output ``C H`` (each 2 x Q
    x d_state x heads x d_head).  The carry across chunks (a few operations
    a state element) is left out."""
    q, n = conf["chunk_size"], conf["ssm_state_size"]
    inner = mamba_inner(conf)
    pairs = q * (q + 1) // 2
    chunk = (2.0 * pairs * (conf["n_groups"] * n + inner)
             + 4.0 * q * n * inner)
    return 3.0 * layers(conf, "M") * chunk / q


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq)
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
    dh = conf["head_dim"]
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    return float(layers(conf, "*") * (6 * q_like + 6 * kv_like))


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every expert
    layer: each HELD row forward, the gradient to it and the gradient to
    its expert's weights, through the expert's two matrices."""
    return (6.0 * rows * seq * layers(conf, "E") * held_per_token(conf)
            * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_moe.py``'s count, over the experts and rows that are here and
    the TWO products an ungated expert has): each product, in each of its
    three passes, reads or writes every held expert's matrix once and reads
    and writes the held rows once."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 2 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["n_routed_experts"] * expert_params(conf) \
        * itemsize
    return float(layers(conf, "E") * (row_bytes + weight_bytes))


def ssd_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the state-space scans of one train step need."""
    return ssd_flops_per_token(conf) * rows * seq


def ssd_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the state-space scans of one train step need, all Mamba
    layers (``flops_hybrid.py``'s reads and writes, from this file's keys):
    forward reads x, B, C, dt and writes y; backward reads x, B, C, dt and
    y's gradient and writes the gradients of x, B, C, dt.  x and y are
    (tokens, heads x d_head), B and C (tokens, n_groups x d_state), dt
    (tokens, heads) in float32.  Not counted: anything a chunked form
    writes and reads back (decay matrices, chunk states), A and D."""
    tokens = rows * seq
    x = tokens * mamba_inner(conf) * itemsize
    bc = 2 * tokens * conf["n_groups"] * conf["ssm_state_size"] * itemsize
    dt = tokens * conf["mamba_num_heads"] * 4
    forward = 2 * x + bc + dt
    backward = 3 * x + 2 * (bc + dt)
    return float(layers(conf, "M") * (forward + backward))
