"""Operations and bytes ONE CHIP'S SHARE of a Nemotron-3 model needs, from
shapes alone: what ``"flops": "flops_nemotron3"`` in a configuration file
names, the yardstick of its ``train_step.mfu_pct``, ``flash_roofline``,
``moe.experts_roofline``, ``ssm_wide.scan_roofline`` and
``lmoe.latent_roofline``.

The model is ``flops_nemotron_h.py``'s (the FIRST ``num_hidden_layers``
characters of ``hybrid_override_pattern``, a layer ONE sub-block: ``M`` a
Mamba-2 mixer, ``*`` softmax attention, ``E`` an expert FFN, ``-`` a dense
one), whose counts of a Mamba and an attention layer are imported, with two
things more:

- the routed experts work in a LATENT of ``moe_latent_size`` ``l``: an
  expert is TWO matrices ``l x m`` and ``m x l`` (never ``d`` wide), and an
  ``E`` layer holds the pair ``d x l`` and ``l x d`` round them, which every
  token meets;
- behind the stack a predicted-ahead module (``num_nextn_predict_layers``
  1): its projection ``2 d x d``, the layers ``mtp_hybrid_override_pattern``
  spells — EVERY count of this file is over the stack's layers AND the
  module's (``layers``) — and a SECOND pass of the head.

Counted is what THIS chip's forward and backward passes REQUIRE
(``n_routed_experts`` the experts held here, ``reduced`` their published
count; ``vocab_size`` the slice): 6 a matmul parameter and token — the
router over ALL the published experts, the latent pair and the shared
expert for every token, the HELD rows in expectation
(``num_experts_per_tok x held / published`` experts a token: 0.34 at 22 x 8
/ 512, which is what random weights and tokens give; ``moe.held_rows_share``
reports what a run had), the module's projection, the head twice —, causal
attention in the ``*`` layers, the state-space scan in the ``M`` layers as
the chunked algorithm needs it.

NOT counted: what ``flops_nemotron_h.py`` leaves out, and the rows of the
static row buffer that name an absent expert.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict

from benchmark import flops_nemotron_h as sibling
from benchmark.flops_nemotron_h import (
    attention_params, mamba_conv_dim, mamba_inner, mamba_params,
    published_experts, shared_params)


def modules(conf: Dict) -> int:
    """Predicted-ahead modules: 0 or 1."""
    return conf.get("num_nextn_predict_layers", 0)


def _unrolled(conf: Dict) -> Dict:
    """``conf`` with the stack's layers that are run and the module's as
    ONE pattern, every character of it run: what the sibling's counts of
    the attention layers and of the scans read."""
    stack = conf["hybrid_override_pattern"][:conf["num_hidden_layers"]]
    module = conf.get("mtp_hybrid_override_pattern", "") * modules(conf)
    return dict(conf, hybrid_override_pattern=stack + module,
                num_hidden_layers=len(stack + module))


def layers(conf: Dict, kind: str) -> int:
    """Layers of ``kind`` (a character of the patterns) that are run: the
    stack's and the module's."""
    return _unrolled(conf)["hybrid_override_pattern"].count(kind)


def latent(conf: Dict) -> int:
    """The width the routed experts read and write."""
    return conf.get("moe_latent_size") or conf["hidden_size"]


def expert_params(conf: Dict) -> int:
    """The TWO matrices of ONE routed expert, in the latent."""
    return 2 * latent(conf) * conf["moe_intermediate_size"]


def latent_params(conf: Dict) -> int:
    """The pair of projections round an expert layer's routed experts."""
    if not conf.get("moe_latent_size"):
        return 0
    return 2 * conf["hidden_size"] * conf["moe_latent_size"]


def held_per_token(conf: Dict) -> float:
    """Experts held here that a token meets, in expectation."""
    return (conf["num_experts_per_tok"] * conf["n_routed_experts"]
            / published_experts(conf))


def active_matmul_params(conf: Dict) -> float:
    """Parameters that multiply one token's activation on this chip."""
    d = conf["hidden_size"]
    experts = (d * published_experts(conf) + shared_params(conf)
               + latent_params(conf)
               + held_per_token(conf) * expert_params(conf))
    return (layers(conf, "M") * mamba_params(conf)
            + layers(conf, "*") * attention_params(conf)
            + layers(conf, "E") * experts
            + layers(conf, "-") * 2 * d * conf["intermediate_size"]
            + modules(conf) * 2 * d * d
            + (1 + modules(conf)) * d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices, the held
    experts, both tables, a Mamba layer's taps with their bias, its three
    numbers a head and its gated norm's weight, every layer's norm, the
    selection biases, the last norm; the module's projection and its three
    norms."""
    d, routed = conf["hidden_size"], published_experts(conf)
    mamba = (mamba_params(conf) + d
             + (conf["conv_kernel"] + 1) * mamba_conv_dim(conf)
             + 3 * conf["mamba_num_heads"] + mamba_inner(conf))
    expert = (d + d * routed + routed + shared_params(conf)
              + latent_params(conf)
              + conf["n_routed_experts"] * expert_params(conf))
    return (layers(conf, "M") * mamba
            + layers(conf, "*") * (attention_params(conf) + d)
            + layers(conf, "E") * expert
            + layers(conf, "-") * (2 * d * conf["intermediate_size"] + d)
            + modules(conf) * (2 * d * d + 3 * d)
            + 2 * d * conf["vocab_size"] + d)


def ssd_flops_per_token(conf: Dict) -> float:
    """The state-space scan, forward and backward, per token, in the Mamba
    layers that are run (``flops_nemotron_h.py``'s count and derivation)."""
    return sibling.ssd_flops_per_token(_unrolled(conf))


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * active_matmul_params(conf)
            + sibling.attention_flops_per_token(_unrolled(conf), seq)
            + ssd_flops_per_token(conf))


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step of ``rows`` x ``seq``
    tokens, forward and backward: the stack's attention layers AND the
    module's (``flops_nemotron_h.py``'s count)."""
    return sibling.flash_step_flops(_unrolled(conf), rows, seq)


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the attention of one train step needs, likewise."""
    return sibling.flash_step_bytes(_unrolled(conf), rows, seq, itemsize)


def ssd_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the state-space scans of one train step need."""
    return sibling.ssd_step_flops(_unrolled(conf), rows, seq)


def ssd_step_bytes(conf: Dict, rows: int, seq: int,
                   itemsize: int = 2) -> float:
    """HBM traffic the state-space scans of one train step need, all Mamba
    layers (``flops_nemotron_h.py``'s reads and writes: x and y ``(tokens,
    heads x d_head)``, B and C ``(tokens, n_groups x d_state)``, dt
    ``(tokens, heads)`` in float32)."""
    return sibling.ssd_step_bytes(_unrolled(conf), rows, seq, itemsize)


def experts_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the grouped products need in one train step, every expert
    layer: each HELD row forward, the gradient to it and the gradient to
    its expert's weights, through the expert's two matrices AT THE LATENT
    WIDTH."""
    return (6.0 * rows * seq * layers(conf, "E") * held_per_token(conf)
            * expert_params(conf))


def experts_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the grouped products of one train step need
    (``flops_nemotron_h.py``'s count at the latent width): each of the two
    products, in each of its three passes, reads or writes every held
    expert's matrix once and reads and writes the held rows once."""
    m = conf["moe_intermediate_size"]
    held_rows = rows * seq * held_per_token(conf)
    row_bytes = 3 * 2 * held_rows * (latent(conf) + m) * itemsize
    weight_bytes = 3 * conf["n_routed_experts"] * expert_params(conf) \
        * itemsize
    return float(layers(conf, "E") * (row_bytes + weight_bytes))


def latent_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What the latent pair needs in one train step, every expert layer:
    every token through ``d x l`` and ``l x d``, forward, the gradient to
    the input and the gradient to the matrix."""
    return 6.0 * rows * seq * layers(conf, "E") * latent_params(conf)


def latent_step_bytes(conf: Dict, rows: int, seq: int,
                      itemsize: int = 2) -> float:
    """HBM traffic the latent pair of one train step needs: each of the two
    products, in each of its three passes (forward, the input's gradient,
    the matrix's), reads or writes the matrix once, the tokens at the
    model's width once and the tokens at the latent's once."""
    d, l = conf["hidden_size"], latent(conf)
    tokens = rows * seq
    one_pass = (tokens * (d + l) + d * l) * itemsize
    return float(layers(conf, "E") * 2 * 3 * one_pass)
