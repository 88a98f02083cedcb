"""Operations and bytes ONE CHIP'S SHARE of Phi-4-mini-flash-reasoning
needs, from shapes alone: what ``"flops": "flops_phi4flash"`` in a
configuration file names, the yardstick of its ``train_step.mfu_pct``,
``flash_roofline``, ``diffattn.roofline`` and ``s6.scan_roofline``.

Which layer is what follows from the depth (``kinds``: the SambaY rule,
``benchmark/reference/phi4flash.py``): Mamba-1 layers, differential
attention under the window and in full, cross layers onto the full layer's
keys and values, gated memory units.  Counted is what forward and backward
REQUIRE of the model the file describes (``vocab_size`` the slice),
WHATEVER implements it:

- 6 a matmul parameter and token: a Mamba layer's four projections, an
  attention layer's four, a cross layer's two (q and o: it makes no key and
  no value), a unit's two, every layer's SwiGLU, the tied table once, as
  the head;
- attention by the (q, k) pairs the mask leaves — the window's in a
  windowed layer, the causal ones in the full and the cross layers — at
  ``pair_flops`` a pair forward: in each q pair two scores over d_head and
  two products with the ONE doubled value head (2 x 2 d_head + 2 x 4
  d_head), 15360 at 20 pairs of 64; backward twice that (``flops.py``'s
  rule: the scores a flash backward remakes are executed, not needed).

NOT counted in ``train_flops_per_token``: the selective scan (elementwise
work for the vector unit, stated apart: ``selscan_step_flops``), the
convolution, norms, gates, softplus, lambda and the pairs' subtraction and
norm; a layer run again under the checkpoint.

``conf`` is a configuration file of ``benchmark/configs`` (the public
``config.json`` key names).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops import head_dim
from benchmark.flops_afmoe import causal_pairs, window_pairs

MAMBA, WINDOWED, FULL, UNIT, CROSS = "M", "W", "F", "G", "C"


def kinds(depth: int) -> str:
    """A character a layer of a model of ``depth`` layers: the reference's
    rule, written out again (the driver's process reads this module and
    may not import JAX, which the reference does;
    ``benchmark/tests/test_phi4flash.py`` holds the two to each other)."""
    half = depth // 2
    return "".join(
        (MAMBA if i <= half else UNIT) if i % 2 == 0
        else WINDOWED if i < half else FULL if i == half + 1 else CROSS
        for i in range(depth))


def layers(conf: Dict, kind: str) -> int:
    return kinds(conf["num_hidden_layers"]).count(kind)


def inner(conf: Dict) -> int:
    return conf["mamba_expand"] * conf["hidden_size"]


def dt_rank(conf: Dict) -> int:
    rank = conf["mamba_dt_rank"]
    return -(-conf["hidden_size"] // 16) if rank == "auto" else rank


def mamba_matmul_params(conf: Dict) -> int:
    """``W_in`` to [xs | z], ``W_x`` to [dt's rank | B | C], ``W_dt``,
    ``W_out``."""
    d, e, n = conf["hidden_size"], inner(conf), conf["mamba_d_state"]
    return d * 2 * e + e * (dt_rank(conf) + 2 * n) + dt_rank(conf) * e + e * d


def attention_matmul_params(conf: Dict, cross: bool = False) -> int:
    """q and o at heads x d_head; k and v at the KV heads' but in a cross
    layer, which has neither."""
    d, dh = conf["hidden_size"], head_dim(conf)
    q = conf["num_attention_heads"] * dh
    kv = 0 if cross else conf["num_key_value_heads"] * dh
    return 2 * d * q + 2 * d * kv


def unit_matmul_params(conf: Dict) -> int:
    return 2 * conf["hidden_size"] * inner(conf)


def matmul_params(conf: Dict) -> int:
    """Parameters that multiply one token's activation."""
    d = conf["hidden_size"]
    return (layers(conf, MAMBA) * mamba_matmul_params(conf)
            + (layers(conf, WINDOWED) + layers(conf, FULL))
            * attention_matmul_params(conf)
            + layers(conf, CROSS) * attention_matmul_params(conf, True)
            + layers(conf, UNIT) * unit_matmul_params(conf)
            + conf["num_hidden_layers"] * 3 * d * conf["intermediate_size"]
            + d * conf["vocab_size"])


def total_params(conf: Dict) -> int:
    """Every parameter the train state holds: the matrices (the tied table
    once); a Mamba layer's convolution with its bias, dt's bias, ``A_log``
    and ``D``; an attention layer's biases, four lambda vectors and the
    pair norm's weight; two LayerNorms a layer and the last one, each a
    weight and a bias."""
    d, dh, e = conf["hidden_size"], head_dim(conf), inner(conf)
    q = conf["num_attention_heads"] * dh
    kv = conf["num_key_value_heads"] * dh
    mamba = (conf["mamba_d_conv"] + 1) * e + e + e * conf["mamba_d_state"] + e
    attention, cross = q + 2 * kv + d + 6 * dh, q + d + 6 * dh
    return (matmul_params(conf) + layers(conf, MAMBA) * mamba
            + (layers(conf, WINDOWED) + layers(conf, FULL)) * attention
            + layers(conf, CROSS) * cross
            + (2 * conf["num_hidden_layers"] + 1) * 2 * d)


def pair_flops(conf: Dict) -> float:
    """Forward, a (q, k) pair of every q pair of one layer: two scores and
    two products with the doubled value head."""
    return 12.0 * head_dim(conf) * (conf["num_attention_heads"] // 2)


def needed_pairs(conf: Dict, seq: int, windowed: bool) -> int:
    """(q, k) pairs a sequence's masks leave, in the windowed layers or in
    the full and the cross ones."""
    if windowed:
        return layers(conf, WINDOWED) * window_pairs(conf, seq)
    return (layers(conf, FULL) + layers(conf, CROSS)) * causal_pairs(seq)


def attention_step_flops(conf: Dict, rows: int, seq: int,
                         windowed: bool) -> float:
    """Forward and backward (twice the forward) of those pairs."""
    return 3.0 * pair_flops(conf) * rows * needed_pairs(conf, seq, windowed)


def flash_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """What attention needs in one train step, every attention layer."""
    return sum(attention_step_flops(conf, rows, seq, w)
               for w in (False, True))


def flash_step_bytes(conf: Dict, rows: int, seq: int,
                     itemsize: int = 2) -> float:
    """HBM traffic the attention of one train step needs (``flops.py``'s
    count: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv), k and v at the KV heads, the values ONCE (a
    call that hands them in twice moves more than it needs), o at the
    pairs' doubled heads after the subtraction."""
    dh = head_dim(conf)
    q_like = rows * seq * conf["num_attention_heads"] * dh * itemsize
    kv_like = rows * seq * conf["num_key_value_heads"] * dh * itemsize
    attending = (layers(conf, WINDOWED) + layers(conf, FULL)
                 + layers(conf, CROSS))
    return float(attending * (6 * q_like + 6 * kv_like))


def train_flops_per_token(conf: Dict, seq: int) -> float:
    """Model FLOPs of one training token on this chip."""
    return (6.0 * matmul_params(conf)
            + flash_step_flops(conf, 1, seq) / seq)


def selscan_step_flops(conf: Dict, rows: int, seq: int) -> float:
    """The selective scans' elementwise operations in one train step, all
    Mamba layers: a state update is ``dt A``, the decay times ``H``, ``B``
    times ``dt x``, their sum, ``C`` times ``H`` and its sum into ``m`` (6 a
    channel and state index; the exponential is no FLOP), a channel ``dt
    x``, ``D x`` and its sum (3); backward twice the forward.  Work for the
    vector unit: NOT part of ``train_flops_per_token``."""
    per_channel = 6.0 * conf["mamba_d_state"] + 3.0
    return (3.0 * layers(conf, MAMBA) * rows * seq * inner(conf)
            * per_channel)


def selscan_step_bytes(conf: Dict, rows: int, seq: int,
                       itemsize: int = 2) -> float:
    """HBM traffic the selective scans of one train step need, all Mamba
    layers: forward reads x, dt (float32), B, C and writes m; backward reads
    x, dt, B, C and m's gradient and writes the gradients of x, dt, B, C.
    Not counted: the states a chunked form writes and reads back, ``A``,
    ``D``, any change of layout round a kernel."""
    tokens = rows * seq
    x = tokens * inner(conf) * itemsize
    dt = tokens * inner(conf) * 4
    bc = 2 * tokens * conf["mamba_d_state"] * itemsize
    return float(layers(conf, MAMBA) * (
        (2 * x + dt + bc) + (3 * x + 2 * dt + 2 * bc)))
