"""Plain reference of the Mellum 2 decoder (``model_type`` ``mellum``;
``config.json`` of huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct), as
ONE CHIP'S SHARE of a layer divided over several where the configuration
file states one.

With ``n(.)`` an RMSNorm with its own learned weight and ``rms_norm_eps``, a
layer ``l`` is

    a = x + Attn_l(n_in(x))
    y = a + Experts_l(n_post(a))                  every layer an expert layer

- ``Attn(h)``: ``q = h W_q`` (heads x d_head), ``k, v = h W_k, h W_v``
  (kv_heads x d_head; query head ``j`` reads KV head ``j // group``); q and
  k are ROTATED IN EVERY LAYER, ``x cos + rot(x) sin`` over the whole head
  (dimension ``i`` paired with ``i + d_head / 2``), by the tables of the
  layer's KIND, ``rope_parameters[layer_types[l]]`` (``rope_tables``):
  ``sliding_attention`` plain, ``inv_freq_i = theta ** (-2i / d_head)``;
  ``full_attention`` YaRN (arXiv:2309.00071 §3.2): ``interp_i = plain_i /
  factor``, ``ramp_i = clip((i - low) / (high - low), 0, 1)`` with ``low =
  floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``c(n) = d_head
  ln(original / (2 pi n)) / (2 ln theta)`` clamped to ``[0, d_head - 1]``,
  ``inv_freq_i = interp_i ramp_i + plain_i (1 - ramp_i)``, and cos and sin
  TIMES ``attention_factor`` (§3.4's temperature, on the tables as the
  ``transformers`` code has it: the scores carry its square).  Causal
  softmax at ``d_head ** -0.5``; in a ``sliding_attention`` layer query
  ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``.
- ``Experts(h)``: ``p = softmax(h W_r)`` over ALL the published experts; the
  ``num_experts_per_tok`` largest; gates ``p_e`` over the sum of the CHOSEN
  (``norm_topk_prob``), whether a chosen expert is held here or not;
  ``sum_e gate_e SwiGLU_e(h)`` at ``moe_intermediate_size``, no shared
  expert.  OF A SHARE the sum runs over the experts HELD (the leading
  dimension of the program's expert tensors, from ``first_expert`` on):
  what an absent expert would add is left out, here as in the program, and
  that partial result goes on to the next layer.
- One last RMSNorm; an untied head (of a share, over the vocabulary's slice).
- Training adds, averaged over the layers, the load-balancing loss ``E
  sum_e (count_e / T) mean_t p[t, e]`` over the router's ``E`` outputs at
  ``router_aux_loss_coef`` (the configuration file's ``assumed``).

Departures from the published description, each stated: the rotate-half
pairing (an interleaved one is a fixed permutation of a head's columns,
which random weights cannot tell apart); NO norm over q and k and NO
predicted-ahead head (the public file has no key for either: ``assumed``);
the public file's ``intermediate_size`` is read by no layer
(``mlp_layer_types`` is ``sparse`` throughout).

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel: the mask is written out, ``(Q_BLOCK, seq)`` of the
``(seq, seq)`` one at a time (``afmoe.py``'s ``masked_attention``: at 16384
tokens no score matrix of 32 heads stands whole, 1.07 GB a block of 512
queries); the experts are a LOOP over the held ones (``xing4.py``'s
``held_experts``); the head runs ``HEAD_BLOCK`` positions at a time
(``xing4.py``'s ``_head_nll``).  It reads the PROGRAM'S parameters as they
lie (``ray_tpu/models/llama.py``: ``layers`` a tuple of stacks, one a
maximal run of layers of one kind — ``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``, ``mlp_norm``, ``router (L, d, E)`` and the three ``(L, E', ...)``)
and upcasts one layer, and inside it one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  The selection of experts is
discontinuous as OLMoE's is (``olmoe.py`` says what that does to the
per-token comparison); a quarter of the experts is held, so one swapped
choice in four changes what this chip adds.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import masked_attention
from benchmark.reference.decoder import apply_rope, rms_norm
from benchmark.reference.granite_hybrid import locate
from benchmark.reference.olmoe import route
from benchmark.reference.xing4 import _head_nll, held_experts

# The tolerance of the MEAN loss at 8192 tokens and more: the other shares'
# (Xing4's, Nemotron-H's, Trinity's).  The configuration file's
# ``check.why`` has the chip readings it stands over.
LOSS_RTOL = 3e-4
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to an expert that is held; the busiest
# expert's load, the share of the rows that is here, the rows the kernels
# visit, what the windowed kernels compute over what the window leaves and
# the share of their sub-tiles that takes a mask are kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None),
                "attn_window_executed_share": ("max", None),
                "attn_window_masked_tile_share": ("max", None)}
S, F = "sliding_attention", "full_attention"


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


def inv_freq(d_head: int, group: Dict):
    """The ``d_head / 2`` frequencies of one ``rope_parameters`` group:
    plain, or YaRN's by parts."""
    theta = float(group["rope_theta"])
    i = jnp.arange(d_head // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / d_head)
    if group.get("rope_type", "default") == "default":
        return plain

    def c(turns):  # the dimension that turns this often in the old range
        return (d_head * math.log(group["original_max_position_embeddings"]
                                  / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(c(group["beta_fast"])), 0)
    high = min(math.ceil(c(group["beta_slow"])), d_head - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / group["factor"] * ramp + plain * (1.0 - ramp)


def rope_tables(seq: int, d_head: int, group: Dict):
    """``(cos, sin) (seq, d_head / 2)`` of one group, times its
    ``attention_factor`` (1 for a plain group)."""
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq(
        d_head, group)[None, :]
    factor = float(group.get("attention_factor", 1.0))
    return factor * jnp.cos(angles), factor * jnp.sin(angles)


def attention(u, p, *, heads, kv_heads, group: Dict, window):
    """``Attn`` on the normed ``u (rows, seq, d)`` with the tables of the
    layer's kind; ``window``: the keys a query sees, or None (all before
    it)."""
    rows, seq, _ = u.shape
    d_head = p["wq"].shape[-1] // heads
    cos, sin = rope_tables(seq, d_head, group)
    q = apply_rope((u @ p["wq"]).reshape(rows, seq, heads, d_head), cos, sin)
    k = apply_rope((u @ p["wk"]).reshape(rows, seq, kv_heads, d_head),
                   cos, sin)
    v = (u @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    return masked_attention(q, k, v, window).reshape(
        rows, seq, heads * d_head) @ p["wo"]


def expert_ffn(h, p, *, k, renormalise, first):
    """The routed experts held here, of the normed ``h (rows, seq, d)``;
    also the experts chosen ``(T, k)`` and the layer's load-balancing
    loss over ALL the router's outputs."""
    rows, seq, d = h.shape
    n = h.reshape(rows * seq, d)
    probs, gates, experts, _ = route(n, p["router"], k, renormalise)
    num_experts = probs.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(experts, num_experts), axis=(0, 1))
    balance = num_experts * jnp.sum(
        counts / n.shape[0] * jnp.mean(probs, axis=0))
    y = held_experts(n, gates, experts, first, p["w_gate"], p["w_up"],
                     p["w_down"])
    return y.reshape(rows, seq, d), experts, balance


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("kinds", "heads", "kv_heads", "groups", "eps", "window", "k",
           "renormalise", "first")


def _one_layer(x, stack, place, kind, kw):
    """One layer of ``kind`` on float32 ``x (rows, seq, d)``; returns ``(x,
    the experts chosen (T, k), the load-balancing loss)``."""
    mixer, _ = kind
    p = {name: a[place] if name in _BIG else a[place].astype(jnp.float32)
         for name, a in stack.items()}
    eps = kw["eps"]
    x = x + attention(
        rms_norm(x, p["attn_norm"], eps), p, heads=kw["heads"],
        kv_heads=kw["kv_heads"], group=dict(dict(kw["groups"])[mixer]),
        window=kw["window"] if mixer == S else None)
    y, experts, balance = expert_ffn(
        rms_norm(x, p["mlp_norm"], eps), p, k=kw["k"],
        renormalise=kw["renormalise"], first=kw["first"])
    return x + y, experts, balance


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
    """(mixer, FFN) of the layers that are run, in order: every FFN the
    expert layer (``mlp_layer_types`` is ``sparse`` throughout)."""
    depth = conf["num_hidden_layers"]
    if set(conf["mlp_layer_types"][:depth]) != {"sparse"}:
        raise NotImplementedError("a layer whose FFN is not 'sparse'")
    return tuple((mixer, "moe") for mixer in conf["layer_types"][:depth])


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names); ``groups`` is ``rope_parameters``
    made hashable."""
    return dict(kinds=kinds(conf), heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                groups=tuple(sorted(
                    (kind, tuple(sorted(group.items())))
                    for kind, group in conf["rope_parameters"].items())),
                eps=float(conf["rms_norm_eps"]),
                window=int(conf["sliding_window"]),
                k=conf["num_experts_per_tok"],
                renormalise=bool(conf["norm_topk_prob"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` (the mean next-token loss), ``aux_loss`` (the load-balancing
    loss, the mean over the layers), ``total`` (the two at the file's
    ``router_aux_loss_coef``), ``token_nll (rows, seq)``, ``experts`` (a
    layer's choices ``(T, k)``, in order) and ``moe_held_share`` (the
    choices that name a held expert over all of them, the mean over the
    layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen, balance = [], 0.0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts, b = _jitted_layer(x, stack, place, kind, **kw)
            chosen.append((experts, stack["w_gate"].shape[1]))
            balance = balance + b / len(kw["kinds"])
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
    first = kw["first"]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e, held in chosen) / len(chosen)
    nll = jnp.mean(token_nll)
    return {"loss": nll, "aux_loss": balance,
            "total": nll + conf["router_aux_loss_coef"] * balance,
            "token_nll": token_nll, "experts": [e for e, _ in chosen],
            "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: cross-entropy plus the load-balancing term."""
    return loss_parts(params, tokens, conf)["total"]
