"""Operations and bytes ONE HOST'S SHARE of a JoyAI-LLM-Flash model needs,
from shapes alone: what ``"flops": "flops_joyai"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline`` and ``moe.exchange_roofline``.

The block is Xing4.0's without the residual streams (``flops_xing4.py``
counts a latent mixer, an expert and the two heads the same way, and those
counts are imported), so what is counted is what the HOST's forward and
backward passes REQUIRE of the model the file describes
(``n_routed_experts`` the experts held on the host, ``reduced`` their
published count; ``vocab_size`` the slice):

- 6 a matmul parameter and token: the latent mixer of every layer, the
  dense FFN of the ``first_k_dense_replace`` leading layers, in every later
  layer the router over ALL the published experts, the shared expert, and
  the HELD rows in expectation (``num_experts_per_tok x held / published``
  experts a token); the head over the slice; the predicted-ahead module
  (its projection, one more expert layer, the head AGAIN);
- causal attention in every layer and the module's, 3 products over the
  q/k head and 3 over ``v_head_dim``.

A token is counted ONCE however many chips the host has: tokens are split
over the ``ep`` ranks outside the expert layer and a row is computed on the
one rank that holds its expert.  ``train_flops_per_token`` and the
``flash_*`` counts are the HOST's (their readers divide by the cell's
chips); ``experts_step_*`` and ``exchange_step_bytes`` are ONE CHIP's, one
rank of the ``ep_ranks`` the file states (their readers take one chip's
time and divide by nothing).

NOT counted: norms, RoPE, sigmoids, SwiGLU's product, the sort and gathers
of the dispatch, the rows of the static row buffer that name an expert of
another rank or host, a layer run again under the checkpoint, the
all-reduce of the data-parallel gradients.
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops_xing4 import (
    attention_flops_per_token, attention_params, blocks, expert_layers,
    expert_params, flash_step_bytes, flash_step_flops, held_per_token,
    published_experts)

__all__ = ["train_flops_per_token", "flash_step_flops", "flash_step_bytes",
           "experts_step_flops", "experts_step_bytes", "exchange_step_bytes"]


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this host."""
    d = conf["hidden_size"]
    dense = conf["first_k_dense_replace"] * 3 * d * conf["intermediate_size"]
    experts = expert_layers(conf) * (
        d * published_experts(conf)
        + conf["n_shared_experts"] * expert_params(conf)
        + held_per_token(conf) * expert_params(conf))
    heads = (1 + conf["num_nextn_predict_layers"]) * d * conf["vocab_size"]
    ahead = conf["num_nextn_predict_layers"] * 2 * d * d
    return blocks(conf) * attention_params(conf) + dense + experts + heads \
        + ahead


def total_params(conf: Dict) -> int:
    """Every parameter the host's train state holds: the held experts, the
    embedding, every norm, the selection biases."""
    d = conf["hidden_size"]
    mixer = (attention_params(conf) + d + conf["q_lora_rank"]
             + conf["kv_lora_rank"])
    dense = 3 * d * conf["intermediate_size"] + d
    routed = published_experts(conf)
    expert = (d * routed + routed + d
              + (conf["n_shared_experts"] + conf["n_routed_experts"])
              * expert_params(conf))
    ahead = conf["num_nextn_predict_layers"] * (2 * d * d + 3 * d)
    return (blocks(conf) * mixer + conf["first_k_dense_replace"] * dense
            + expert_layers(conf) * expert + 2 * d * conf["vocab_size"] + d
            + ahead)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this host."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq))


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What ONE CHIP's grouped products need in one train step of ``rows``
    x ``seq`` tokens on the host, every expert layer: each row that lands
    on an expert of this rank forward, the gradient to it and the gradient
    to its expert's weights (a rank holds ``1 / ep_ranks`` of the host's
    experts and, in expectation, of its held rows)."""
    return (6.0 * rows * seq * expert_layers(conf) * held_per_token(conf)
            * expert_params(conf)) / conf["ep_ranks"]


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic ONE CHIP's grouped products need in one train step
    (``flops_moe.py``'s count, over the experts and rows of one rank): each
    of the three products, in each of its three passes, reads or writes
    every expert's matrix of the rank once and reads and writes its rows
    once."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 3 * held_rows * (d + m) * itemsize
    weight_bytes = 3 * conf["n_routed_experts"] * expert_params(conf) \
        * itemsize
    return float(expert_layers(conf) * (row_bytes + weight_bytes)
                 / conf["ep_ranks"])


def needs_rank_share(conf: Dict) -> float:
    """The share of the tokens that choose AT LEAST ONE expert of a given
    rank: a token's ``num_experts_per_tok`` distinct choices among the
    published experts, uniform as random weights and tokens leave them,
    miss the rank's ``held / ep_ranks`` with probability ``C(E - r, k) /
    C(E, k)``."""
    e, k = published_experts(conf), conf["num_experts_per_tok"]
    rank = conf["n_routed_experts"] // conf["ep_ranks"]
    missed = 1.0
    for i in range(k):
        missed *= (e - rank - i) / (e - i)
    return 1.0 - missed


def exchange_step_bytes(conf: Dict, rows: int, seq: int,
                        itemsize: int = 2) -> float:
    """The bytes ONE CHIP must receive and send over the interconnect in
    one train step for the tokens that OTHER ranks own and its experts are
    expected to need, from shapes alone and whatever form the program gives
    the exchange: a rank owns ``rows x seq / ep_ranks`` tokens; of the other
    ranks' tokens ``needs_rank_share`` choose one of its experts and must
    reach it once, ``hidden_size`` numbers each (received), and their parts
    go back (sent); backward the parts' cotangents come (received) and the
    tokens' go back (sent): 4 transfers of ``(ep - 1) x T_local x share x
    d`` numbers a layer.  The chip's OWN tokens travel the other way at the
    same time (as many bytes sent where these are received), which a
    full-duplex link carries beside them: one direction's bytes over the
    published interconnect rate is the least time, and a rate that is the
    sum of both directions only makes the share read lower.  Not counted:
    the choices and gates that travel with a token (``2 k`` numbers beside
    ``d``), the forward pass run again under the checkpoint."""
    ranks = conf["ep_ranks"]
    others = (ranks - 1) * rows * seq / ranks
    one_way = others * needs_rank_share(conf) * conf["hidden_size"] * itemsize
    return float(expert_layers(conf) * 4 * one_way)
