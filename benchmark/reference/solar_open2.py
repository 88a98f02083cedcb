"""Plain reference of the Solar-Open-2 decoder (``model_type``
``solar_open2``; ``config.json`` of huggingface.co/upstage/Solar-Open2-250B),
as ONE CHIP'S SHARE of a layer divided over several where the configuration
file states one.  A pre-norm residual ``x + mixer(RMSNorm(x))``, ``x +
FFN(RMSNorm(x))`` (``rms_norm_eps``), a final norm, an untied head; each
part from its source:

- **Which layer is what**: ``gqa_layers`` lists the SOFTMAX layers, counted
  FROM 0 (the published list stays whole in a file that cuts the depth:
  entries past ``num_hidden_layers`` name layers that are not run); every
  other layer is a KDA layer.  ``first_k_dense_replace`` 0: every layer's
  FFN is the expert layer (``intermediate_size`` is read by no layer).
- **The KDA mixer** (Kimi Delta Attention, arXiv:2510.26692 §3, as
  flash-linear-attention's ``fla/layers/kda.py`` has it), per head of
  ``linear_attn_config.num_heads``, keys and values ``head_dim`` wide
  (``num_kv_heads`` null: as many value heads): ``kimi_linear.py``'s mixer
  to the letter — one input projection ``[q | k | v | f | gate | b]``, a
  causal depthwise convolution of ``short_conv_kernel_size`` taps without
  bias and SiLU on q, k, v, L2 norms a head (q then times ``head_dim **
  -0.5``), the log-decay of every KEY CHANNEL ``g = -exp(A_log_head)
  softplus(f W_f_up + dt_bias)``, the state ``S' = Diag(exp g_t) S_(t-1)``,
  ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``, ``o =
  RMSNorm_head(o) * sigmoid(gate W_g_up)``, the output projection — but for
  the write strength: ``kda_allow_neg_eigval`` true makes it ``beta = 2
  sigmoid(b)``, in (0, 2) (that code's ``beta * 2.``), so that the
  transition ``Diag(alpha) (I - beta k k^T)`` has eigenvalues in (-1, 1).
- **The softmax mixer**: ``q, k, v = h W_q, h W_k, h W_v``
  (``num_attention_heads`` / ``num_key_value_heads`` / the same x
  ``head_dim``; query head ``j`` reads KV head ``j // group``), NO rotation
  and no other position signal (``use_rope`` false), causal softmax of ``q
  k^T head_dim ** -0.5`` times v, ``o = W_o [attn * sigmoid(h W_g)]`` with
  ``W_g`` as wide as ``W_q`` (``use_gqa_gate`` true: a gate a head and
  channel of the output).
- **The expert layer** (the family's: Solar Open's ``solar_open`` code is
  GLM-4-MoE's, DeepSeek-V3's gate, arXiv:2412.19437 §2.1.2): ``s =
  sigmoid(h W_r)`` over ALL ``n_routed_experts`` (published count); the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` reaches the selection
  only); gates ``routed_scaling_factor x s_i / sum of the chosen s``
  (``norm_topk_prob``); ``y = sum g_i E_i(h) + E_shared(h)``, every E a
  SwiGLU of ``moe_intermediate_size`` (the shared one ``n_shared_experts``
  times that).  OF A SHARE the sum runs over the experts HELD
  (``xing4.held_experts``: the leading dimension of the program's expert
  tensors, from ``first_expert`` on): what an absent expert would add is
  left out, here as in the program, and that partial result goes on to the
  next layer; the vocabulary's slice is a smaller vocabulary.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The state recurrence runs ONE TOKEN AT A TIME
(``kimi_linear.kda_recurrence``, a ``lax.scan`` over positions): no chunk,
no level, no triangular inverse, no cumulative sum — nothing of the chunked
algorithm under test (``ray_tpu/ops/delta.py``).  No kernel: the causal
mask is written out (``afmoe.masked_attention`` without a window, ``Q_BLOCK``
queries at a time only to bound the scores' memory), the experts are a loop
over the held ones, the head runs ``HEAD_BLOCK`` positions at a time.  It
reads the PROGRAM'S parameters as they lie (``layers`` a tuple of stacks,
one a run of layers of one (mixer, FFN) kind, in the model's order; a KDA
stack holds ``kda_norm``, ``kda_in``, ``kda_conv_w``, ``kda_f_up``,
``kda_dt_bias``, ``kda_A_log``, ``kda_g_up``, ``kda_gate_norm``,
``kda_out``; a softmax one ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wg``,
``wo``) and upcasts one layer, and inside it one expert, at a time.

DEPARTURES from the public description, each under the configuration's
``assumed`` with what would settle it (``modeling_solar_open2.py`` and the
checkpoint's tensor names):
- ``kda_use_full_proj`` false is read as "the decay and the output gate
  come up from a low rank of ``head_dim``", the paper's form and the only
  one the flag's ``false`` can leave; ``true`` is refused here.
- the output gate's width (``W_g`` hidden x heads x ``head_dim``,
  elementwise): the public file has ``use_gqa_gate`` and no size;
- the expert layer's scoring, selection bias and the shared expert's width
  are the family's, not the file's;
- the input projection is ONE matrix where the published code has six (a
  fixed permutation of columns); no convolution bias; L2 epsilon 1e-6; no
  epsilon under the gates' sum.
The selection bias's UPDATE is the train step's and no part of the loss.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``STEP_METRICS``: no step
may lose an assignment to a held expert; the busiest expert's load, the
held share, ``kda_beta_max`` (the largest write strength of the step: over
1 says the negative-eigenvalue path ran), ``kda_state_absmax`` and
``kda_chunk_decay_min`` (the window keeps with ``max`` the MILDEST step's,
the train loop having no ``min``) are kept and held to no value.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import masked_attention
from benchmark.reference.decoder import rms_norm
from benchmark.reference.granite_hybrid import locate
from benchmark.reference.kimi_linear import _unit, kda_recurrence
from benchmark.reference.olmo_hybrid import _head_nll
from benchmark.reference.xing4 import expert_ffn

# The tolerance of the MEAN loss at the cell's 4096 tokens and more (the
# other shares' 3e-4 at 8192: a thirty-second of the experts is held, so few
# swapped choices reach this chip's sum; the configuration file's
# ``check.why`` has the chip readings it stands over, 5.2e-5 at the most).
LOSS_RTOL = 3e-4

STEP_METRICS: Dict[str, Any] = {"moe_dropped": ("sum", 0.0),
                                "moe_load_max_over_mean": ("max", None),
                                "moe_held_share": ("max", None),
                                "kda_beta_max": ("max", None),
                                "kda_state_absmax": ("max", None),
                                "kda_chunk_decay_min": ("max", None)}


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    4096 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (4096 / tokens) ** 0.5)


def kinds(conf: Dict) -> Tuple[Tuple[str, str], ...]:
    """(mixer, FFN) of the layers that are run, in order."""
    return tuple(("attention" if i in conf["gqa_layers"] else "kda",
                  "dense" if i < conf["first_k_dense_replace"] else "moe")
                 for i in range(conf["num_hidden_layers"]))


def kda_mixer(x, p, *, kda_heads, kda_dim, beta_scale, eps):
    """What a KDA layer adds to ``x (rows, seq, d)``; the write strength is
    ``beta_scale x sigmoid``."""
    rows, seq, _ = x.shape
    inner = kda_heads * kda_dim
    proj = rms_norm(x, p["kda_norm"], eps) @ p["kda_in"]
    qkv, f, gate, b = (proj[..., :3 * inner],
                       proj[..., 3 * inner:3 * inner + kda_dim],
                       proj[..., 3 * inner + kda_dim:-kda_heads],
                       proj[..., -kda_heads:])
    width, channels = p["kda_conv_w"].shape
    qkv = jax.nn.silu(jax.lax.conv_general_dilated(  # no bias
        qkv, p["kda_conv_w"][:, None, :], window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision=jax.lax.Precision.HIGHEST))

    def heads(t):
        return t.reshape(rows, seq, kda_heads, kda_dim)

    q = _unit(heads(qkv[..., :inner])) * kda_dim ** -0.5
    k = _unit(heads(qkv[..., inner:2 * inner]))
    v = heads(qkv[..., 2 * inner:])
    g = -jnp.exp(p["kda_A_log"])[:, None] * jax.nn.softplus(
        heads(f @ p["kda_f_up"] + p["kda_dt_bias"]))
    o = rms_norm(kda_recurrence(q, k, v, g, beta_scale * jax.nn.sigmoid(b)),
                 p["kda_gate_norm"], eps)
    o = o * jax.nn.sigmoid(heads(gate @ p["kda_g_up"]))
    return o.reshape(rows, seq, inner) @ p["kda_out"]


def softmax_mixer(x, p, *, heads, kv_heads, eps):
    """What a softmax layer adds: grouped KV heads, no position signal,
    the output gate."""
    rows, seq, _ = x.shape
    h = rms_norm(x, p["attn_norm"], eps)
    d_head = p["wq"].shape[-1] // heads
    q = (h @ p["wq"]).reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    k = (h @ p["wk"]).reshape(rows, seq, kv_heads, d_head)
    v = (h @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    o = masked_attention(q, k, v, None).reshape(rows, seq, heads * d_head)
    return (o * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]


_MIXER_KW = {"kda": ("kda_heads", "kda_dim", "beta_scale", "eps"),
             "attention": ("heads", "kv_heads", "eps")}
_STATIC = ("kinds", "kda_heads", "kda_dim", "beta_scale", "heads",
           "kv_heads", "eps", "k", "factor", "first")
_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time


def _one_layer(x, kind, stack, place, kw):
    """One layer of ``kind`` on the stream ``x (rows, seq, d)``; also the
    experts its tokens chose ``(T, k)``."""
    mixer, _ = kind
    p = {name: a[place] if name in _BIG
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    mix = kda_mixer if mixer == "kda" else softmax_mixer
    x = x + mix(x, p, **{name: kw[name] for name in _MIXER_KW[mixer]})
    y, experts = expert_ffn(x, p, k=kw["k"], factor=kw["factor"],
                            first=kw["first"], eps=kw["eps"])
    return x + y, experts


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on the float32 stream ``x
    (rows, seq, d)``, whatever its kind; ``layers`` the program's stacks;
    ``kw`` is ``layer_kwargs``'.  Index 0, which ``rehearse_compile.py``
    compiles, is the softmax layer."""
    kind, stack, place = locate(kw["kinds"], layers)[index]
    return _one_layer(x, kind, stack, place, kw)[0]


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    linear = conf["linear_attn_config"]
    if (conf["use_rope"] or not conf["use_gqa_gate"]
            or conf["kda_use_full_proj"] or conf["first_k_dense_replace"]
            or linear["num_kv_heads"] not in (None, linear["num_heads"])):
        raise NotImplementedError(
            "rotated or ungated softmax layers, full-rank KDA gates, "
            "leading dense layers or fewer value heads than key heads")
    return dict(
        kinds=kinds(conf), kda_heads=linear["num_heads"],
        kda_dim=linear["head_dim"],
        beta_scale=2.0 if conf["kda_allow_neg_eigval"] else 1.0,
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        eps=float(conf["rms_norm_eps"]), k=conf["num_experts_per_tok"],
        factor=float(conf["routed_scaling_factor"]),
        first=int(conf.get("first_expert", 0)))


@functools.partial(jax.jit, static_argnums=(1,), static_argnames=_STATIC)
def _jitted_layer(x, kind, stack, place, **kw):
    """``place`` is traced: one program a stack, not one a layer."""
    return _one_layer(x, kind, stack, place, kw)


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` = ``total`` (the mean next-token loss), ``token_nll (rows,
    seq)``, ``experts`` (a layer's choices ``(T, k)``) and
    ``moe_held_share`` (the choices that name a held expert over all of
    them, the mean over the layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen, held = [], 0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, kind, stack, place, **kw)
            chosen.append(experts)
            held = stack["w_gate"].shape[1]
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
    first = kw["first"]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e in chosen) / len(chosen)
    nll = jnp.mean(token_nll)
    return {"loss": nll, "total": nll, "token_nll": token_nll,
            "experts": chosen, "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: mean next-token cross-entropy."""
    return loss_parts(params, tokens, conf)["total"]
