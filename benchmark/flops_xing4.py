"""Operations and bytes ONE CHIP'S SHARE of a Xing4.0 model needs, from
shapes alone: what ``"flops": "flops_xing4"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``hc.mix_roofline``.

Counted is what THIS chip's forward and backward passes REQUIRE of the
model the file describes (``n_routed_experts`` the experts held here,
``reduced`` their published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: the latent mixer of every layer (both
  down-projections, both up-projections, the output), the dense FFN of the
  ``first_k_dense_replace`` leading layers, in every later layer the
  router over ALL the published experts, the shared expert, and the HELD
  rows in expectation — ``num_experts_per_tok x held / published`` experts a
  token, which is what random weights and tokens give (``moe.held_rows_
  share`` reports what a run had) —, the maps' projection of each of a
  layer's two blocks, the head over the slice; the predicted-ahead module
  (its projection, one more expert layer, the head AGAIN: two heads a
  step);
- causal attention in every layer and the module's, 3 products over the
  q/k head (``qk_nope_head_dim + qk_rope_head_dim``: the scores and both
  their gradients) and 3 over ``v_head_dim``;
- the residual's mixing sums (``hc_step_flops``), which no MXU runs.

NOT counted: norms, RoPE, sigmoids, Sinkhorn, SwiGLU's product, the sort
and gathers of the dispatch, the rows of the static row buffer that name
an absent expert, a layer run again under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict


def blocks(conf: Dict) -> int:
    """Layers that are run: the model's and one a predicted-ahead module."""
    return conf["num_hidden_layers"] + conf["num_nextn_predict_layers"]


def expert_layers(conf: Dict) -> int:
    return blocks(conf) - conf["first_k_dense_replace"]


def published_experts(conf: Dict) -> int:
    cut = conf.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else conf["n_routed_experts"]


def qk_dim(conf: Dict) -> int:
    return conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def attention_params(conf: Dict) -> int:
    """The five matrices of a latent mixer."""
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return (d * conf["q_lora_rank"]
            + conf["q_lora_rank"] * heads * qk_dim(conf)
            + d * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            + conf["kv_lora_rank"] * heads * (conf["qk_nope_head_dim"]
                                              + conf["v_head_dim"])
            + heads * conf["v_head_dim"] * d)


def expert_params(conf: Dict) -> int:
    """The three SwiGLU matrices of ONE routed expert."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def map_params(conf: Dict) -> int:
    """The projection of ONE block's maps: n d x (n + n + n^2)."""
    n = conf["hc_mult"]
    return n * conf["hidden_size"] * (2 * n + n * n)


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_tok"] * conf["n_routed_experts"]
            / published_experts(conf))


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    dense = conf["first_k_dense_replace"] * 3 * d * conf["intermediate_size"]
    experts = expert_layers(conf) * (
        d * published_experts(conf)
        + conf["n_shared_experts"] * expert_params(conf)
        + held_per_token(conf) * expert_params(conf))
    heads = (1 + conf["num_nextn_predict_layers"]) * d * conf["vocab_size"]
    ahead = conf["num_nextn_predict_layers"] * 2 * d * d
    return (blocks(conf) * (attention_params(conf) + 2 * map_params(conf))
            + dense + experts + heads + ahead)


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the held experts, the
    embedding, every norm, the maps' biases and scales, the selection
    biases."""
    d, n = conf["hidden_size"], conf["hc_mult"]
    maps = 2 * (map_params(conf) + 2 * n + n * n + 3)
    mixer = (attention_params(conf) + d + conf["q_lora_rank"]
             + conf["kv_lora_rank"])
    dense = 3 * d * conf["intermediate_size"] + d
    routed = published_experts(conf)
    expert = (d * routed + routed + d
              + (conf["n_shared_experts"] + conf["n_routed_experts"])
              * expert_params(conf))
    ahead = conf["num_nextn_predict_layers"] * (2 * d * d + 3 * d)
    return (blocks(conf) * (mixer + maps)
            + conf["first_k_dense_replace"] * dense
            + expert_layers(conf) * expert
            + 2 * d * conf["vocab_size"] + d + ahead)


def attention_flops_per_token(conf: Dict, seq: int) -> float:
    """Causal self-attention, forward and backward, per token
    (``flops.py`` has the derivation: 6 products of 2 x seq x width a head
    and query, half of them under the mask): the scores and their two
    gradients over the q/k head, the output and its two over v's."""
    return (3.0 * blocks(conf) * seq * conf["num_attention_heads"]
            * (qk_dim(conf) + conf["v_head_dim"]))


def mix_flops_per_token(conf: Dict) -> float:
    """The residual's sums, forward and backward (forward x 3), a token:
    a block reads ``Hpre X`` (2 n d), and writes ``Hres X + Hpost^T y``
    (2 n^2 d + 2 n d); two blocks a layer."""
    n, d = conf["hc_mult"], conf["hidden_size"]
    return 3.0 * 2 * blocks(conf) * (2 * n * d * (n + 2))


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq)
            + mix_flops_per_token(conf))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x ``seq``
    tokens, every layer and the module's, forward and backward."""
    return attention_flops_per_token(conf, seq) * rows * seq


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic that attention needs: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv.  q is heads x
    the q/k head; k is what the MODEL has, heads x ``qk_nope_head_dim`` and
    the one shared rotary head (a kernel that reads it laid beside every
    head's own part moves more than it needs); v and o heads x
    ``v_head_dim``."""
    heads, tokens = conf["num_attention_heads"], rows * seq
    q = tokens * heads * qk_dim(conf) * itemsize
    k = tokens * (heads * conf["qk_nope_head_dim"]
                  + conf["qk_rope_head_dim"]) * itemsize
    v = tokens * heads * conf["v_head_dim"] * itemsize
    return float(blocks(conf) * 3 * (q + k + 2 * v))


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
    weight_bytes = 3 * conf["n_routed_experts"] * expert_params(conf) \
        * itemsize
    return float(expert_layers(conf) * (row_bytes + weight_bytes))


def hc_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the residual (maps' projection and the mixing sums) needs in
    one train step."""
    per_token = (6.0 * 2 * blocks(conf) * map_params(conf)
                 + mix_flops_per_token(conf))
    return per_token * rows * seq


def hc_step_bytes(conf: Dict, rows: int, seq: int,
                  itemsize: int = 2) -> float:
    """The LEAST HBM traffic of the streams in one train step: a block
    cannot be fused into its maps, so round it the streams ``X (n d)`` are
    read once before it (maps and ``x = Hpre X`` from the one read; ``x``
    written) and once after it (with ``y``; ``X'`` written): forward ``3 n
    d + 2 d`` numbers a token and block.  Backward, after the block: read
    ``dX'``, ``X``, ``y``, write the part of ``dX`` that goes round the
    block and ``dy`` (``3 n d + 2 d``); before it: read that part, ``X``
    and ``dx``, write ``dX`` (``3 n d + d``).  ``9 n d + 5 d`` in all, two
    blocks a layer.  Not counted: the maps themselves (``n^2 + 2 n``
    numbers a token), the forward pass run again under the checkpoint."""
    n, d = conf["hc_mult"], conf["hidden_size"]
    return float(2 * blocks(conf) * rows * seq * (9 * n * d + 5 * d)
                 * itemsize)
