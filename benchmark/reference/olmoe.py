"""Plain reference of the OLMoE decoder (arXiv:2409.02060; ``config.json``
and ``modeling_olmoe.py`` of huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct).

Per layer, on ``x (rows, seq, d)``, every norm an RMSNorm with a learned
weight and the configuration's ``rms_norm_eps``:

- attention: ``n = norm1(x)``; ``q = q_norm(n Wq)``, ``k = k_norm(n Wk)``,
  ``v = n Wv``, where ``q_norm`` and ``k_norm`` run over the WHOLE
  projection (before the split into heads, before RoPE); rotate-half RoPE
  on q and k; causal softmax attention; ``x = x + o Wo``.
- experts: ``n = norm2(x)``; ``logits = n Wr``; ``p = softmax(logits)``
  over the experts; the ``num_experts_per_tok`` largest ``p`` and their
  experts, NOT renormalised (``norm_topk_prob`` false; renormalised where
  a configuration says true); ``y = sum_j g_j * (silu(n Wgate[e_j]) *
  (n Wup[e_j])) Wdown[e_j]``; ``x = x + y``.  Every token gets all of its
  experts.
- training adds, averaged over the layers, the load-balancing loss
  ``E * sum_e (count_e / T) * mean_t p[t, e]`` (``count_e`` over all the
  choices of every token) and the router z-loss ``mean_t
  logsumexp(logits_t)^2``, at the weights ``router_aux_loss_coef`` and
  ``router_z_loss_coef`` of the configuration file (which says under
  ``assumed`` where they and its ``qk_norm`` come from).

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  Nothing is imported from
``ray_tpu``; the helpers shared with the dense reference (RMSNorm, RoPE,
blocked causal attention, the head's loss) come from ``decoder.py`` beside
this file.  The experts are a LOOP over all of them: expert ``e`` is
applied to every token and enters with the weight ``sum_j g_j * [e_j ==
e]``, which is 0 for a token that did not choose it — no sort, no gather,
no kernel, so nothing here shares a mechanism with the code under test.
It reads the program's parameters as they lie (``decoder.py``'s list,
plus ``q_norm (L, h*dh)``, ``k_norm (L, kv*dh)``, ``router (L, d, E)``,
``w_gate``/``w_up (L, E, d, m)``, ``w_down (L, E, m, d)``) and upcasts one
layer, and inside it one expert, at a time.

Tolerance (``LOSS_RTOL``), on the total loss of a 4096-token sample.  As
for the dense decoder the program computes in bfloat16 activations with
float32 norms' statistics, router, softmaxes, logits and losses, this
file in float32: per-token losses differ by about 1e-2 with either sign
and their mean by about 1e-5 relative (``decoder.py``).  The expert layer
adds one thing the dense block has not: the choice of experts is a
discontinuous function of the router's input.  Where a token's 8th and
9th probabilities lie closer than the bfloat16 rounding of its normed
activations moves them (about 2**-8 relative on logits of order 1), the
program and this file send the token to different 8th experts.  The two
candidates then carry nearly the same gate (that is why they swapped), a
gate near 1/64 where the largest are several times that, so the token's
expert output changes by about its smallest of eight terms, in a few
tokens of a hundred, with either sign: the effect on a 4096-token mean
is of the order of the rounding noise itself.  On the v5e, at the
published widths and depth 2, the choices differed in 1.4-1.9 % of the
tokens in the first layer and 2.3-3.0 % in the second, always in one
expert of the eight, and the total loss by 4.2e-7 to 1.8e-5 relative
(my chip runs, PR 25: 16 checks, 14 seeds); the tolerance is 1e-4 as
for the dense decoder.  What
should fail it (``tests/test_moe.py`` shows each at a tiny size, with
norm weights drawn away from 1, as the train loop draws them for its
check — at step 0 they are all 1 and what they norm has unit RMS, so a
missing QK-norm alone would not show): a missing QK-norm, the z-loss
(0.2 % of the total here) or the load-balancing loss (0.7 %) left out,
one expert of the eight left out or a dropped token, gates
renormalised, a router in bfloat16 (more swaps, and gates off by 2**-8).
PRECISION is the per-token comparison's to see (``decoder.py``, point 1):
with the swapped experts in it the program reads 0.0071-0.0082 nats RMS
on the v5e, this file with 8-bit float matrices 0.046, with int8 ones
0.0175 (PR 29, 12 seeds); the configuration file holds the limit.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

# LOSS_RTOL and loss_rtol: the dense decoder's tolerance holds here too.
from benchmark.reference.decoder import (  # noqa: F401
    LOSS_RTOL, _head_nll, apply_rope, causal_attention, loss_rtol, rms_norm,
    rope_tables)

# What the window fetches with every loss (``decoder.py`` has the form):
# no step may drop an assignment; the busiest expert's load is kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None)}


def route(n, router, k: int, renormalise: bool):
    """``n (T, d)`` -> (probabilities ``(T, E)``, gates ``(T, k)``, experts
    ``(T, k)``, logits)."""
    logits = n @ router
    p = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(p, k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return p, gates, experts, logits


def experts_loop(n, gates, experts, w_gate, w_up, w_down):
    """``sum_e weight_e * expert_e(n)`` over ALL experts, one at a time;
    ``weight_e (T,)`` is the token's gate for ``e``, or 0."""
    def one(y, ws):
        e, wg, wu, wd = ws
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        out = (jax.nn.silu(n @ wg) * (n @ wu)) @ wd
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "k", "renormalise", "qk_norm"))
def layer(x, layers, index, *, heads, kv_heads, theta, eps, k,
          renormalise=False, qk_norm=True):
    """One OLMoE layer on float32 ``x (rows, seq, d)``; returns ``(x,
    load-balancing loss, z-loss, experts (T, k))`` of layer ``index``."""
    p = {name: a[index] for name, a in layers.items()}
    big = ("w_gate", "w_up", "w_down")  # upcast one expert at a time
    p = {name: a if name in big else a.astype(jnp.float32)
         for name, a in p.items()}
    rows, seq, d = x.shape
    d_head = p["wq"].shape[-1] // heads
    cos, sin = rope_tables(seq, d_head, theta)
    h = rms_norm(x, p["attn_norm"], eps)
    q, kk = h @ p["wq"], h @ p["wk"]
    if qk_norm:
        q = rms_norm(q, p["q_norm"], eps)
        kk = rms_norm(kk, p["k_norm"], eps)
    q = apply_rope(q.reshape(rows, seq, heads, d_head), cos, sin)
    kk = apply_rope(kk.reshape(rows, seq, kv_heads, d_head), cos, sin)
    v = (h @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    o = causal_attention(q, kk, v).reshape(rows, seq, heads * d_head)
    x = x + o @ p["wo"]

    n = rms_norm(x, p["mlp_norm"], eps).reshape(rows * seq, d)
    probs, gates, experts, logits = route(n, p["router"], k, renormalise)
    num_experts = probs.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(experts, num_experts), axis=(0, 1))
    balance = num_experts * jnp.sum(
        counts / n.shape[0] * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    y = experts_loop(n, gates, experts, p["w_gate"], p["w_up"], p["w_down"])
    return x + y.reshape(rows, seq, d), balance, z, experts


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``."""
    return dict(heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                k=conf["num_experts_per_tok"],
                renormalise=bool(conf["norm_topk_prob"]),
                qk_norm=bool(conf["qk_norm"]))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """``loss`` (mean next-token cross-entropy), ``aux_loss`` and
    ``z_loss`` (means over the layers), ``total`` (the three at the
    configuration's weights), ``token_nll`` (each position's next-token
    loss) and ``experts`` (per layer, ``(T, k)``) of
    ``tokens (rows, seq + 1)`` under the configuration file ``conf``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    depth = conf["num_hidden_layers"]
    eps = float(conf["rms_norm_eps"])
    balance = z = 0.0
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for i in range(depth):
            x, b_i, z_i, e_i = layer(x, params["layers"], i,
                                     **layer_kwargs(conf))
            balance, z = balance + b_i / depth, z + z_i / depth
            chosen.append(e_i)
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=eps)
    nll = jnp.mean(token_nll)
    total = (nll + conf["router_aux_loss_coef"] * balance
             + conf["router_z_loss_coef"] * z)
    return {"loss": nll, "aux_loss": balance, "z_loss": z, "total": total,
            "token_nll": token_nll, "experts": chosen}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: cross-entropy plus both auxiliary terms."""
    return loss_parts(params, tokens, conf)["total"]
