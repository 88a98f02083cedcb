"""Operations and bytes a LOOPED decoder needs (Ouro: one stack of layers
run ``total_ut_steps`` times over the same weights, an exit gate and the
head read after every pass), from shapes alone: what ``"flops":
"flops_ouro"`` in a configuration file names, the yardstick of its
``train_step.mfu_pct``, ``flash_roofline`` and ``ut.head_loss_roofline``.

``flops.py``'s rule on what a token passes through: with ``T`` passes over
``N`` layers a token meets ``T x N`` layer applications — q, k, v, o and
the three SwiGLU matrices, and the causal attention's NEEDED pairs at the
job's sequence length, each ``T`` times —, ``T`` head products (every pass's
logits enter the loss) and ``T - 1`` reads of the exit gate (the last
pass's gate decides nothing: what is left exits there).  6 a matmul
parameter and use (2 forward, 4 backward).

NOT counted: norms, RoPE, the softmaxes, the exit distribution; a layer run
again under the layer checkpoint and a pass's logits made again for the
backward pass are executed, not needed.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names; ``total_ut_steps`` the passes).
"""

from __future__ import annotations

from typing import Dict

from benchmark import flops
from benchmark.flops import head_dim


def passes(conf: Dict) -> int:
    return conf["total_ut_steps"]


def layer_matmul_params(conf: Dict) -> int:
    """Parameters that multiply an activation in ONE layer application."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d + 3 * d * conf["intermediate_size"]


def head_params(conf: Dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds, each ONCE however often it is
    used: the layers' matrices and four norms each, embedding and untied
    head, the last norm, the exit gate and its bias."""
    d = conf["hidden_size"]
    return (conf["num_hidden_layers"] * (layer_matmul_params(conf) + 4 * d)
            + 2 * head_params(conf) + d + d + 1)


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token: every use of every matrix, and
    causal attention in every layer application."""
    t = passes(conf)
    used = (t * conf["num_hidden_layers"] * layer_matmul_params(conf)
            + t * head_params(conf) + (t - 1) * conf["hidden_size"])
    return 6.0 * used + t * flops.attention_flops_per_token(conf, seq)


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What causal attention needs in one train step: ``T x N`` calls,
    forward and backward."""
    return passes(conf) * flops.flash_step_flops(conf, rows, seq)


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic those calls need (``flops.py``'s count a call)."""
    return passes(conf) * flops.flash_step_bytes(conf, rows, seq, itemsize)


def head_step_flops(conf: Dict, tokens: int) -> float:
    """What the ``T`` exits' head products need in one train step of
    ``tokens`` tokens: a forward product and the two of its backward pass
    each.  The logits a pass makes AGAIN for its backward pass are executed,
    not needed."""
    return 6.0 * passes(conf) * tokens * head_params(conf)
