"""Plain reference of the LFM2-MoE decoder (``model_type`` ``lfm2_moe``;
``config.json`` of huggingface.co/LiquidAI/LFM2-8B-A1B), as ONE CHIP'S SHARE
of a layer divided over several where the configuration file states one.

Every layer is ``x <- x + op(RMSNorm(x))``, ``x <- x + ffn(RMSNorm(x))``,
each norm with its own learned weight and ``norm_eps``; one last RMSNorm
before the head, which reads the embedding table (tied).  ``layer_types``
names each layer's ``op``, ``num_dense_layers`` how many leading layers
have a dense FFN.  With ``u`` the normed input of a token ``t``:

- ``conv`` (the gated short convolution): ``[B | C | x] = W_in u`` (d -> 3
  d, in that order, no bias); ``z = B * x``; ``c_t = sum_i w_i z_(t - (L-1)
  + i)`` for ``i`` in ``0 .. L-1`` with ``L = conv_L_cache`` taps a channel
  (depthwise, zeros before the sequence, ``w_(L-1)`` on the current token,
  ``conv_bias`` false, NO activation); ``y = C * c``; ``op = W_out y``.
  Here the convolution is ``L`` shifted products, added.
- ``full_attention``: ``q = W_q u`` (heads x d_head), ``k, v = W_k u, W_v
  u`` (kv_heads x d_head; query head ``j`` reads KV head ``j // group``);
  an RMSNorm over EACH head's d_head numbers of q and of k with one
  learned weight of d_head each, BEFORE the rotary embedding (``rope_theta``
  over the whole head, dimension ``i`` paired with ``i + d_head / 2``);
  causal softmax at ``d_head ** -0.5``; ``W_o``.
- the FFN of a leading layer: SwiGLU of ``intermediate_size``.  Of a later
  layer, with ``h`` the normed input: ``s = sigmoid(W_r h)`` over ALL the
  published experts; the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``use_expert_bias``: ``b`` reaches the selection only); gates
  ``g = s`` at those, ``g <- g / (sum g + 1e-6)`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``ffn = sum_e g_e W2_e (silu(W1_e h) *
  W3_e h)`` at ``moe_intermediate_size``.  No shared expert, no auxiliary
  loss.  OF A SHARE the sum runs over the experts HELD (the leading
  dimension of the program's expert tensors, from ``first_expert`` on):
  what an absent expert would add is left out, here as in the program, and
  that partial result goes on to the next layer.

The configuration file lists under ``assumed`` what the catalog row does
not settle (the order ``[B | C | x]``, the per-head norms and their place
before RoPE, the ``1e-6``, the tied head, RoPE's pairing): each with what
would settle it.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The experts are a LOOP over the held ones, each applied to
every token at the weight ``sum_j g_j [e_j == e]`` — no sort, no gather,
no kernel (``xing4.py``'s ``held_experts``); attention is computed for
``Q_BLOCK`` queries at a time (``decoder.py``) and the tied head for
``HEAD_BLOCK`` positions (``granite_hybrid.py``'s, with its ``locate``
for the runs), only to bound memory.  It reads the PROGRAM'S
parameters as they lie (``ray_tpu/models/llama.py``: ``layers`` a tuple of
stacks, one a maximal run of layers of one kind (op, FFN) — ``sconv_norm``,
``sconv_in (L, d, 3 d)``, ``sconv_w (L, taps, d)``, ``sconv_out`` of a
``conv`` layer; ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``
and ``k_norm (L, d_head)`` of an attention layer; ``mlp_norm`` and
``w_gate``, ``w_up``, ``w_down`` of a dense FFN, or ``router (L, d, E)``,
``router_bias (L, E)`` and the three ``(L, E', ...)`` of an expert layer)
and upcasts one layer, and inside it one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  The selection of experts
is discontinuous as OLMoE's and Xing4's is (``olmoe.py`` says what that
does to the per-token comparison); a chosen expert enters at a gate near
0.25 with HALF the experts held, so both comparisons are the noisiest of
any cell: the tolerance of the mean is this file's (``LOSS_RTOL``), the
limit of the per-token comparison the configuration file's, each from chip
readings.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (
    apply_rope, causal_attention, rms_norm, rope_tables)
from benchmark.reference.granite_hybrid import _tied_head_nll, locate
from benchmark.reference.xing4 import held_experts, swiglu

# The tolerance of the MEAN loss at 8192 tokens and more.  Here the
# per-token losses stand 0.094-0.101 nats apart from this file's (swapped
# experts with half of them held: the configuration file's ``check``), so a
# mean of 8192 of them has a noise of 0.098 / sqrt(8192) = 1.1e-3 nats,
# 1.0e-4 of a loss of 10.9: the v5e read 2e-6 to 1.97e-4 over 51 checks
# (PR 40), where Xing4's 3e-4 would be 3 standard deviations and refuse
# one run in 300-400.  6e-4 is 6 of them and 3.0 times the largest sound
# reading.  At that width the mean guards only GROSS structure (a term of
# the loss dropped or added): on the chip five faults in the program read
# 4e-5 to 1.8e-3, among the sound readings (call ``p40_3``: no per-head
# norm, the selection bias zeroed, the taps reversed, gates not
# renormalised, softmax scores).  The PER-TOKEN comparison is what sees
# them: 0.21 to 1.43 nats RMS against the limit's 0.14 for four of the
# five — and 0.129-0.130 for the dropped per-head norm, UNDER the limit:
# that one is held by tier-1 alone (``tests/test_lfm2.py``, float32).
LOSS_RTOL = 6e-4
TOPK_EPS = 1e-6   # in the division that renormalises the chosen gates
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to an expert that is held; the busiest
# expert's load, the share of the rows that is here and the rows the
# kernels visit are kept.
def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None)}


def short_conv(u, p):
    """The ``conv`` operator on the normed ``u (rows, seq, d)``."""
    d = u.shape[-1]
    bcx = u @ p["sconv_in"]
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = gate_in * x
    taps, seq = p["sconv_w"].shape[0], u.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(p["sconv_w"][i] * padded[:, i:i + seq] for i in range(taps))
    return (gate_out * c) @ p["sconv_out"]


def attention(u, p, *, heads, kv_heads, theta, eps):
    """The ``full_attention`` operator on the normed ``u``."""
    rows, seq, _ = u.shape
    d_head = p["wq"].shape[-1] // heads
    cos, sin = rope_tables(seq, d_head, theta)
    q = rms_norm((u @ p["wq"]).reshape(rows, seq, heads, d_head),
                 p["q_norm"], eps)
    k = rms_norm((u @ p["wk"]).reshape(rows, seq, kv_heads, d_head),
                 p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = apply_rope(q, cos, sin).reshape(
        rows, seq, kv_heads, heads // kv_heads, d_head)
    o = causal_attention(q, apply_rope(k, cos, sin), v)
    return o.reshape(rows, seq, heads * d_head) @ p["wo"]


def route(n, router, bias, k: int, factor: float):
    """``n (T, d)`` -> gates and experts ``(T, k)``: sigmoid scores, the
    ``k`` largest of score + bias, gates the chosen scores over their sum
    plus ``TOPK_EPS``, times ``factor``."""
    scores = jax.nn.sigmoid(n @ router)
    _, experts = jax.lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return (factor * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                               + TOPK_EPS), experts)


def expert_ffn(h, p, *, k, factor, first):
    """The routed experts held here, of the normed ``h (rows, seq, d)``;
    also the experts chosen ``(T, k)``."""
    rows, seq, d = h.shape
    n = h.reshape(rows * seq, d)
    gates, experts = route(n, p["router"], p["router_bias"], k, factor)
    y = held_experts(n, gates, experts, first, p["w_gate"], p["w_up"],
                     p["w_down"])
    return y.reshape(rows, seq, d), experts


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("kinds", "heads", "kv_heads", "theta", "eps", "k", "factor",
           "first")


def _one_layer(x, stack, place, kind, kw):
    """One layer of ``kind`` on float32 ``x (rows, seq, d)``; returns ``(x,
    the experts chosen (T, k) or None)``."""
    op, ffn = kind
    p = {name: a[place] if name in _BIG and ffn == "moe"
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    eps = kw["eps"]
    if op == "conv":
        x = x + short_conv(rms_norm(x, p["sconv_norm"], eps), p)
    else:
        x = x + attention(rms_norm(x, p["attn_norm"], eps), p,
                          heads=kw["heads"], kv_heads=kw["kv_heads"],
                          theta=kw["theta"], eps=eps)
    h = rms_norm(x, p["mlp_norm"], eps)
    if ffn == "dense":
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    y, experts = expert_ffn(h, p, k=kw["k"], factor=kw["factor"],
                            first=kw["first"])
    return x + y, experts


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``, whatever its kind; ``kw`` is ``layer_kwargs``'."""
    kind, stack, place = locate(kw["kinds"], layers)[index]
    return _one_layer(x, stack, place, kind, kw)[0]


@functools.partial(jax.jit, static_argnums=(3,), static_argnames=_STATIC)
def _jitted_layer(x, stack, place, kind, **kw):
    """``place`` is traced: one program a stack, not one a layer."""
    return _one_layer(x, stack, place, kind, kw)


def kinds(conf: Dict) -> Tuple[Tuple[str, str], ...]:
    """(op, FFN) of the layers that are run, in order."""
    return tuple(
        (op, "dense" if i < conf["num_dense_layers"] else "moe")
        for i, op in enumerate(
            conf["layer_types"][:conf["num_hidden_layers"]]))


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(kinds=kinds(conf), heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                theta=float(conf["rope_theta"]), eps=float(conf["norm_eps"]),
                k=conf["num_experts_per_tok"],
                factor=float(conf["routed_scaling_factor"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` = ``total`` (the mean next-token loss: the model adds no
    auxiliary term), ``token_nll (rows, seq)``, ``experts`` (a layer that
    has them: ``(T, k)``) and ``moe_held_share`` (the choices that name a
    held expert over all of them, the mean over those layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, stack, place, kind, **kw)
            if experts is not None:
                chosen.append((experts, stack["w_gate"].shape[1]))
        token_nll = _tied_head_nll(x, params["final_norm"], params["embed"],
                                   targets, eps=kw["eps"], scaling=1.0)
    first = kw["first"]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e, held in chosen) / max(len(chosen), 1)
    nll = jnp.mean(token_nll)
    return {"loss": nll, "total": nll, "token_nll": token_nll,
            "experts": [e for e, _ in chosen], "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: mean next-token cross-entropy."""
    return loss_parts(params, tokens, conf)["total"]
