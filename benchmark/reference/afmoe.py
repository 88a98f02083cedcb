"""Plain reference of the AFMoE decoder (``model_type`` ``afmoe``;
``config.json`` of huggingface.co/arcee-ai/Trinity-Large-Preview), as ONE
CHIP'S SHARE of a layer divided over several where the configuration file
states one.

With ``n(.)`` an RMSNorm with its own learned weight and ``rms_norm_eps``, a
layer ``l`` is

    a = x + n_post_attn(Attn_l(n_in(x)))
    y = a + n_post_mlp(FFN_l(n_pre_mlp(a)))             FOUR norms a layer

- ``Attn(h)``: ``q = h W_q`` (heads x d_head), ``k, v = h W_k, h W_v``
  (kv_heads x d_head; query head ``j`` reads KV head ``j // group``), ``g =
  h W_g`` (heads x d_head); an RMSNorm over EACH head's d_head numbers of q
  and of k, one learned weight of d_head each.  ``layer_types[l]`` says the
  rest: ``sliding_attention`` rotates q and k (``rope_theta`` over the whole
  head, dimension ``i`` paired with ``i + d_head / 2``) and query ``i`` sees
  key ``j`` iff ``0 <= i - j < sliding_window``; ``full_attention`` carries
  NO position signal and sees every ``j <= i``.  ``o = softmax(q k^T /
  sqrt(d_head)) v``; ``Attn = (o * sigmoid(g)) W_o``: the output gate.
- ``FFN_l`` of a layer before ``num_dense_layers``: SwiGLU of
  ``intermediate_size``.  Of a later one: ``s = sigmoid(h W_r)`` over ALL the
  published experts; the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``b``: the selection bias, which reaches the selection only);
  gates ``w_e = s_e / (sum of the chosen s + 1e-20)`` (``route_norm``) times
  ``route_scale``; ``FFN = SwiGLU_shared(h) + sum_e w_e SwiGLU_e(h)`` at
  ``moe_intermediate_size`` (the shared one ``num_shared_experts`` times
  that).  The SUM of both goes through ``n_post_mlp``.  OF A SHARE the sum
  runs over the experts HELD (the leading dimension of the program's expert
  tensors, from ``first_expert`` on): what an absent expert would add is
  left out, here as in the program, and that partial result goes on to the
  next layer.
- The embedded tokens are multiplied by ``sqrt(hidden_size)``
  (``mup_enabled``); one last RMSNorm; an untied head.

The configuration file lists under ``assumed`` what the catalog row does not
settle (the gate and its place, the per-head norms before RoPE, which
layers rotate, the four norms, the ``1e-20``, RoPE's pairing): each from the
public ``afmoe`` modelling file of ``transformers``.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel: the mask is written out, ``(Q_BLOCK, seq)`` of the
``(seq, seq)`` one at a time — attention is computed for ``Q_BLOCK`` queries
against every key, only to bound the score matrix's memory (a window saves
this file nothing); the experts are a LOOP over the held ones
(``xing4.py``'s ``held_experts``); the head runs ``HEAD_BLOCK`` positions at
a time (``xing4.py``'s ``_head_nll``).  It reads the PROGRAM'S parameters as
they lie (``ray_tpu/models/llama.py``: ``layers`` a tuple of stacks, one a
maximal run of layers of one kind (mixer, FFN) — ``attn_norm``,
``attn_post_norm``, ``wq``, ``wk``, ``wv``, ``wg``, ``wo``, ``q_norm`` and
``k_norm (L, d_head)``; ``mlp_norm``, ``mlp_post_norm`` and ``w_gate``,
``w_up``, ``w_down`` of a dense FFN, or ``router (L, d, E)``, ``router_bias
(L, E)``, the three ``(L, E', ...)`` and ``shared_gate``, ``shared_up``,
``shared_down`` of an expert layer) and upcasts one layer, and inside it
one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  The selection of experts is
discontinuous as OLMoE's and Xing4's is (``olmoe.py`` says what that does to
the per-token comparison); a thirty-second of the experts is held, so one
swapped choice in thirty-two changes what this chip adds.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import apply_rope, rms_norm, rope_tables
from benchmark.reference.granite_hybrid import locate
from benchmark.reference.xing4 import _head_nll, held_experts, swiglu

# The tolerance of the MEAN loss at 8192 tokens and more: Xing4's and
# Nemotron-H's, whose shares are an eighth (here a thirty-second: fewer
# swapped choices reach this chip's sum).  The configuration file's
# ``check.why`` has the chip readings it stands over.
LOSS_RTOL = 3e-4
TOPK_EPS = 1e-20  # in the division that renormalises the chosen gates
Q_BLOCK = 512     # 48 heads x 512 x 8192 float32 scores: 0.8 GB a block
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to an expert that is held; the busiest
# expert's load, the share of the rows that is here, the rows the kernels
# visit and what the windowed kernels compute over what the window leaves
# are kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None),
                "attn_window_executed_share": ("max", None)}


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


def masked_attention(q, k, v, window: Optional[int]):
    """q: (rows, seq, kv_heads, group, d_head); k, v: (rows, seq, kv_heads,
    d_head).  Softmax over the keys ``j`` that query ``i`` sees: ``j <= i``
    and, under a ``window``, ``i - j < window``."""
    seq, d_head = q.shape[1], q.shape[-1]
    key_pos = jnp.arange(seq)[None, :]
    out = []
    for start in range(0, seq, Q_BLOCK):
        qb = q[:, start:start + Q_BLOCK]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / math.sqrt(d_head)
        query_pos = (start + jnp.arange(qb.shape[1]))[:, None]
        visible = key_pos <= query_pos
        if window is not None:
            visible = visible & (query_pos - key_pos < window)
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def attention(u, p, *, heads, kv_heads, theta, eps, window: Optional[int]):
    """``Attn`` on the normed ``u (rows, seq, d)``; ``window``: the layer
    is a ``sliding_attention`` one (it rotates q and k), else None."""
    rows, seq, _ = u.shape
    d_head = p["wq"].shape[-1] // heads
    q = rms_norm((u @ p["wq"]).reshape(rows, seq, heads, d_head),
                 p["q_norm"], eps)
    k = rms_norm((u @ p["wk"]).reshape(rows, seq, kv_heads, d_head),
                 p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    if window is not None:
        cos, sin = rope_tables(seq, d_head, theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    o = masked_attention(q, k, v, window).reshape(rows, seq, heads * d_head)
    return (o * jax.nn.sigmoid(u @ p["wg"])) @ p["wo"]


def route(n, router, bias, k: int, scale: float):
    """``n (T, d)`` -> gates and experts ``(T, k)``: sigmoid scores, the
    ``k`` largest of score + bias, gates the chosen scores over their sum
    plus ``TOPK_EPS``, times ``scale``."""
    scores = jax.nn.sigmoid(n @ router)
    _, experts = jax.lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return (scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                              + TOPK_EPS), experts)


def expert_ffn(h, p, *, k, scale, first):
    """The shared expert plus the routed experts held here, of the normed
    ``h (rows, seq, d)``; also the experts chosen ``(T, k)``."""
    rows, seq, d = h.shape
    n = h.reshape(rows * seq, d)
    gates, experts = route(n, p["router"], p["router_bias"], k, scale)
    y = held_experts(n, gates, experts, first, p["w_gate"], p["w_up"],
                     p["w_down"])
    y = y + swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(rows, seq, d), experts


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("kinds", "heads", "kv_heads", "theta", "eps", "window", "k",
           "scale", "first")


def _one_layer(x, stack, place, kind, kw):
    """One layer of ``kind`` on float32 ``x (rows, seq, d)``; returns ``(x,
    the experts chosen (T, k) or None)``."""
    mixer, ffn = kind
    p = {name: a[place] if name in _BIG and ffn == "moe"
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    eps = kw["eps"]
    x = x + rms_norm(attention(
        rms_norm(x, p["attn_norm"], eps), p, heads=kw["heads"],
        kv_heads=kw["kv_heads"], theta=kw["theta"], eps=eps,
        window=kw["window"] if mixer == "sliding_attention" else None),
        p["attn_post_norm"], eps)
    h = rms_norm(x, p["mlp_norm"], eps)
    if ffn == "dense":
        y, experts = swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        y, experts = expert_ffn(h, p, k=kw["k"], scale=kw["scale"],
                                first=kw["first"])
    return x + rms_norm(y, p["mlp_post_norm"], eps), experts


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
    """(mixer, FFN) of the layers that are run, in order."""
    return tuple(
        (mixer, "dense" if i < conf["num_dense_layers"] else "moe")
        for i, mixer in enumerate(
            conf["layer_types"][:conf["num_hidden_layers"]]))


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(kinds=kinds(conf), heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                window=int(conf["sliding_window"]),
                k=conf["num_experts_per_tok"],
                scale=float(conf["route_scale"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` = ``total`` (the mean next-token loss: the model adds no
    auxiliary term), ``token_nll (rows, seq)``, ``experts`` (an expert
    layer's choices ``(T, k)``, in order) and ``moe_held_share`` (the
    choices that name a held expert over all of them, the mean over those
    layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        if conf["mup_enabled"]:
            x = x * math.sqrt(conf["hidden_size"])
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, stack, place, kind, **kw)
            if experts is not None:
                chosen.append((experts, stack["w_gate"].shape[1]))
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
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
