"""Plain reference of the Nemotron-H decoder (``model_type`` ``nemotron_h``;
``config.json`` of huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
the family's report arXiv:2504.03624), as ONE CHIP'S SHARE of a layer
divided over several where the configuration file states one.

THE STACK ``config.json`` DESCRIBES, UNDER THE NEXT-TOKEN LOSS.  The
catalog's description of that release adds a second, denoising tower
(modulated norms, attention that is bidirectional inside a block,
conditioning of one tower on the other, decoding by diffusion over blocks).
None of it has a key in ``config.json`` and none of it is here.

``hybrid_override_pattern`` spells the layers, a character each, and the
model is its first ``num_hidden_layers`` characters.  A layer is ONE
sub-block on the residual, ``x <- x + f(RMSNorm(x; w, layer_norm_epsilon))``
with a norm weight of its own; no bias anywhere but the convolution's; one
last RMSNorm; embedding and head are two tables.  With ``h`` the normed
input:

- ``M``, a Mamba-2 mixer (arXiv:2405.21060): ``[z | xBC | dt] = h W_in``,
  widths ``heads * d_head`` | ``heads * d_head + 2 * n_groups * d_state`` |
  ``heads`` (the inner width is ``mamba_num_heads * mamba_head_dim``,
  whatever ``expand`` says); ``xBC = silu(conv(xBC))``, a causal depthwise
  convolution of ``conv_kernel`` taps WITH bias whose last tap meets the
  current token; ``xBC`` splits into ``x`` (heads x d_head), ``B`` and ``C``
  (``n_groups`` x d_state: head ``i`` reads group ``i // (heads /
  n_groups)``); ``dt = softplus(dt + dt_bias)`` (``time_step_limit`` (0,
  inf): no clamp), ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_(t-1)
  + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))
  * w`` with the GATE FIRST and the norm over EACH GROUP'S ``inner /
  n_groups`` channels on its own; ``f = y W_out``.
- ``*``, softmax attention: ``q = h W_q`` (heads x head_dim), ``k, v = h
  W_k, h W_v`` (kv_heads x head_dim; query head ``j`` reads KV head ``j //
  (heads / kv_heads)``), causal softmax at ``head_dim ** -0.5``, NO rotary
  embedding, ``f = o W_o``.
- ``E``, experts: ``s = sigmoid(h W_r)`` over ALL the published experts in
  float32; the ``num_experts_per_tok`` largest of ``s + b`` (``n_group`` =
  ``topk_group`` = 1: no group limit; the selection bias ``b`` reaches the
  selection only); gates ``s_e / (sum + 1e-20) * routed_scaling_factor``;
  ``f = sum_e gate_e relu(h W_up,e) ** 2 W_down,e + relu(h W_up,s) ** 2
  W_down,s``: NO gate matrix (``mlp_hidden_act`` ``relu2``), routed width
  ``moe_intermediate_size``, shared width
  ``moe_shared_expert_intermediate_size``.  No auxiliary loss.  OF A SHARE
  the sum runs over the experts HELD (the leading dimension of the
  program's expert tensors, from ``first_expert`` on): what an absent expert
  would add is left out, here as in the program, and that partial result
  goes on to the next layer.
- ``-``, a dense relu^2 FFN of ``intermediate_size`` (the pattern in hand
  has none).

The configuration file lists under ``assumed`` what the catalog row does
not settle (no rotary embedding, the order ``[z | xBC | dt]``, the norm by
groups with the gate first, the sigmoid and its bias, the ``1e-20``), each
with what would settle it.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The state recurrence runs ONE TOKEN AT A TIME
(``granite_hybrid.recurrence``: no chunk, no decay matrix, no cumulative
sum); the experts are a LOOP over the held ones, each applied to every
token at the weight ``sum_j g_j [e_j == e]`` — no sort, no gather, no
kernel; attention is computed for ``Q_BLOCK`` queries at a time, only to
bound memory; the head and each position's loss are ``decoder.py``'s (the
slice's float32 logits are 0.5 GB a sequence).  It reads the
PROGRAM'S parameters as they lie (``ray_tpu/models/llama.py``: ``layers`` a
tuple of stacks, one a maximal run of layers of one kind — ``ssm_norm``,
``ssm_in (L, d, [z|xBC|dt])``, ``conv_w (L, taps, channels)``, ``conv_b``,
``dt_bias``, ``A_log``, ``D (L, heads)``, ``gate_norm (L, inner)``,
``ssm_out`` of an ``M`` layer; ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``
of a ``*`` layer; ``mlp_norm``, ``router (L, d, E)``, ``router_bias (L,
E)``, ``w_up (L, E', d, m)``, ``w_down (L, E', m, d)``, ``shared_up``,
``shared_down`` of an ``E`` layer) and upcasts one layer, and inside it
one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``; layer 0, which
``rehearse_compile.py`` compiles, is a Mamba layer.  The selection of
experts is discontinuous (``olmoe.py`` says what that does to the
per-token comparison); a chosen expert enters at a gate near 2.5 / 6 with
an EIGHTH of the experts held.  The tolerance of the mean is this file's
(``LOSS_RTOL``), the limit of the per-token comparison the configuration
file's, each from chip readings.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import _head_nll, rms_norm
from benchmark.reference.granite_hybrid import (
    causal_attention, locate, recurrence)

# The tolerance of the MEAN loss at 8192 tokens and more: Xing4's (an
# eighth of the experts held, gates near a half).  Here the per-token
# losses stand 0.047-0.060 nats apart from this file's (the configuration
# file's ``check``), so a mean of 8192 of them has a noise of 0.054 /
# sqrt(8192) = 6e-4 nats, 5.8e-5 of a loss of 10.3: the v5e read 6.7e-6 to
# 9.7e-5 over 15 checks (PR 48), a third of the tolerance at most.
LOSS_RTOL = 3e-4
TOPK_EPS = 1e-20  # in the division that renormalises the chosen gates
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to an expert that is held; the busiest
# expert's load, the share of the rows that is here and the rows the
# kernels visit are kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None)}


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


def relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def mamba(h, p, *, ssm_heads, d_head, d_state, groups, eps):
    """The ``M`` sub-block on the normed ``h (rows, seq, d)``."""
    rows, seq, _ = h.shape
    inner, gn = ssm_heads * d_head, groups * d_state
    proj = h @ p["ssm_in"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    width, channels = p["conv_w"].shape
    xbc = jax.nn.silu(p["conv_b"] + jax.lax.conv_general_dilated(
        xbc, p["conv_w"][:, None, :], window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision=jax.lax.Precision.HIGHEST))
    xs = xbc[..., :inner].reshape(rows, seq, ssm_heads, d_head)
    b, c = (jnp.repeat(t.reshape(rows, seq, groups, d_state),
                       ssm_heads // groups, 2)
            for t in (xbc[..., inner:inner + gn], xbc[..., inner + gn:]))
    y = recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]), b, c, p["D"])
    # gate first, then each group's channels normed on their own
    gated = (y.reshape(rows, seq, inner) * jax.nn.silu(z)).reshape(
        rows, seq, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return (normed.reshape(rows, seq, inner) * p["gate_norm"]) @ p["ssm_out"]


def attention(h, p, *, heads, kv_heads):
    """The ``*`` sub-block on the normed ``h``: no position signal."""
    rows, seq, _ = h.shape
    d_head = p["wq"].shape[-1] // heads
    q = (h @ p["wq"]).reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    k = (h @ p["wk"]).reshape(rows, seq, kv_heads, d_head)
    v = (h @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    o = causal_attention(q, k, v, d_head ** -0.5)
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


def held_experts(n, gates, experts, first: int, w_up, w_down):
    """``sum_e weight_e * expert_e(n)`` over the experts HELD (``first``
    on, as many as ``w_up`` has), one at a time; ``weight_e (T,)`` is the
    token's gate for ``e``, or 0."""
    def one(y, ws):
        e, wu, wd = ws
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        return y + weight[:, None] * relu2(
            n, wu.astype(jnp.float32), wd.astype(jnp.float32)), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (first + jnp.arange(w_up.shape[0]), w_up, w_down))
    return y


def expert_ffn(h, p, *, k, factor, first):
    """The ``E`` sub-block on the normed ``h``: the routed experts held
    here plus the shared expert; also the experts chosen ``(T, k)``."""
    rows, seq, d = h.shape
    n = h.reshape(rows * seq, d)
    gates, experts = route(n, p["router"], p["router_bias"], k, factor)
    y = held_experts(n, gates, experts, first, p["w_up"], p["w_down"])
    y = y + relu2(n, p["shared_up"], p["shared_down"])
    return y.reshape(rows, seq, d), experts


_BIG = ("w_up", "w_down")  # an expert stack: upcast one at a time
_NORM = {"M": "ssm_norm", "*": "attn_norm", "E": "mlp_norm", "-": "mlp_norm"}
_STATIC = ("kinds", "eps", "heads", "kv_heads", "ssm_heads", "d_head",
           "d_state", "groups", "k", "factor", "first")


def _one_layer(x, stack, place, kind, kw):
    """One layer of ``kind`` on float32 ``x (rows, seq, d)``; returns ``(x,
    the experts chosen (T, k) or None)``."""
    p = {name: a[place] if name in _BIG and kind == "E"
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    h, experts = rms_norm(x, p[_NORM[kind]], kw["eps"]), None
    if kind == "M":
        y = mamba(h, p, eps=kw["eps"], **{name: kw[name] for name in (
            "ssm_heads", "d_head", "d_state", "groups")})
    elif kind == "*":
        y = attention(h, p, heads=kw["heads"], kv_heads=kw["kv_heads"])
    elif kind == "E":
        y, experts = expert_ffn(h, p, k=kw["k"], factor=kw["factor"],
                                first=kw["first"])
    else:
        y = relu2(h, p["w_up"], p["w_down"])
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


def kinds(conf: Dict) -> Tuple[str, ...]:
    """The characters of the layers that are run, in order."""
    return tuple(
        conf["hybrid_override_pattern"][:conf["num_hidden_layers"]])


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(kinds=kinds(conf), eps=float(conf["layer_norm_epsilon"]),
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                ssm_heads=conf["mamba_num_heads"],
                d_head=conf["mamba_head_dim"],
                d_state=conf["ssm_state_size"], groups=conf["n_groups"],
                k=conf["num_experts_per_tok"],
                factor=float(conf["routed_scaling_factor"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` = ``total`` (the mean next-token loss: the model adds no
    auxiliary term), ``token_nll (rows, seq)``, ``experts`` (an ``E``
    layer's choices ``(T, k)``, in order) and ``moe_held_share`` (the
    choices that name a held expert over all of them, the mean over those
    layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, stack, place, kind, **kw)
            if experts is not None:
                chosen.append((experts, stack["w_up"].shape[1]))
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
