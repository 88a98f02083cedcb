"""Plain reference of the Kimi-Linear decoder (``model_type``
``kimi_linear``; ``config.json`` of
huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; Kimi Linear,
arXiv:2510.26692), as ONE CHIP'S SHARE of a layer divided over several
where the configuration file states one.  A pre-norm residual ``x +
mixer(RMSNorm(x))``, ``x + FFN(RMSNorm(x))`` (``rms_norm_eps``), a final
norm, an untied head; each part from its source:

- **Which layer is what**: ``linear_attn_config.kda_layers`` and
  ``.full_attn_layers`` count layers FROM 1 (the published lists stay whole
  in a file that cuts the depth: entries past ``num_hidden_layers`` name
  layers that are not run); the ``first_k_dense_replace`` leading layers
  have a dense SwiGLU of ``intermediate_size``, every later one the expert
  layer (``moe_layer_freq`` 1).
- **The KDA mixer** (Kimi Delta Attention, §3 of the paper), per head of
  ``linear_attn_config.num_heads``, keys and values ``head_dim`` wide: ``[q
  | k | v | f | gate | b] = h W_in`` (widths heads x head_dim three times,
  ``head_dim`` twice — the low ranks —, heads); ``[q | k | v]`` through a
  causal depthwise convolution of ``short_conv_kernel_size`` taps WITHOUT
  bias whose last tap meets the current token, then SiLU; ``q = q / |q| *
  head_dim ** -0.5`` and ``k = k / |k|`` per head (``|.| = sqrt(sum of
  squares + 1e-6)``); ``beta = sigmoid(b)``; the log-decay of every KEY
  CHANNEL ``g = -exp(A_log_head) softplus(f W_f_up + dt_bias)`` in ``(heads,
  head_dim)``, ``alpha = exp(g)``; the state (keys x values) ``S' =
  Diag(alpha_t) S_(t-1)``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,
  ``o_t = S_t^T q_t``; ``o = RMSNorm_head(o) * sigmoid(gate W_g_up)`` (ONE
  norm weight of ``head_dim``); output projection.
- **The latent mixer** (arXiv:2412.19437 §2.1.1 with ``q_lora_rank`` null
  and ``mla_use_nope`` true): ``q = h W_q`` -> heads x [nope | rope]; ``[c_kv
  | k_r] = h W_kva``; ``[k_nope | v] = RMSNorm(c_kv) W_kvb``; ``k = [k_nope |
  k_r for every head]`` — NO rotation of either rotary part: they are 64
  more columns without a position; causal softmax of ``q k^T (nope +
  rope)^-0.5`` times v; ``W_o``.
- **The expert layer**: ``s = sigmoid(h W_r)`` over ALL ``num_experts``
  (published count); the ``num_experts_per_token`` largest of ``s + b``
  (``num_expert_group`` 1: plain top-k; ``b`` reaches the selection only);
  gates ``routed_scaling_factor x s_i / sum of the chosen s``
  (``moe_renormalize``); ``y = sum g_i E_i(h) + E_shared(h)``, every E a
  SwiGLU of ``moe_intermediate_size``.  OF A SHARE the sum runs over the
  experts HELD (``xing4.held_experts``): what an absent expert would add is
  left out, here as in the program.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The state recurrence runs ONE TOKEN AT A TIME (``lax.scan``
over positions): no chunk, no level, no triangular inverse, no cumulative
sum — nothing of the chunked algorithm under test (``ray_tpu/ops/delta.py``).
The convolution is ``lax.conv_general_dilated``; attention is computed for
``Q_BLOCK`` queries at a time and the head for ``HEAD_BLOCK`` positions,
only to bound memory.  It reads the PROGRAM'S parameters as they lie
(``layers`` a tuple of stacks, one a run of layers of one (mixer, FFN)
kind, in the model's order; a KDA stack holds ``kda_norm``, ``kda_in``,
``kda_conv_w (L, taps, 3 x inner)``, ``kda_f_up``, ``kda_dt_bias (L,
inner)``, ``kda_A_log (L, heads)``, ``kda_g_up``, ``kda_gate_norm``,
``kda_out``; a latent one ``attn_norm``, ``wq``, ``wkv_a``, ``kv_a_norm``,
``wkv_b``, ``wo``) and upcasts one layer, and inside it one expert, at a
time.

What the public ``config.json`` does not settle and this file (with the
configuration's ``assumed``) sets — ``modeling_kimi.py`` and
flash-linear-attention's ``kda.py`` would settle each: the order of the
input projection's parts (one matrix where the published code has six: a
fixed permutation of columns); no convolution bias; the decay's form and
``A_log`` a head / ``dt_bias`` a channel; the output gate's sigmoid,
without bias, and both low ranks = ``head_dim``; the L2 epsilon 1e-6; no
epsilon under the gates' sum (the published 1e-20 is below float32's
resolution of a sum of eight sigmoids).  The selection bias's UPDATE is the
train step's and no part of the loss.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``STEP_METRICS``: no step
may lose an assignment to a held expert; the busiest expert's load, the
held share, ``kda_state_absmax`` (the largest ``|S|`` at a chunk's end) and
``kda_chunk_decay_min`` (the most negative cumulative log-decay inside a
chunk of the step; the window keeps with ``max`` the MILDEST step's, the
train loop having no ``min``) are kept and held to no value.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import rms_norm
from benchmark.reference.granite_hybrid import locate
from benchmark.reference.olmo_hybrid import _head_nll
from benchmark.reference.xing4 import (
    LOSS_RTOL, causal_attention, expert_ffn, loss_rtol, swiglu)  # noqa: F401

L2_EPS = 1e-6
STEP_METRICS: Dict[str, Any] = {"moe_dropped": ("sum", 0.0),
                                "moe_load_max_over_mean": ("max", None),
                                "moe_held_share": ("max", None),
                                "kda_state_absmax": ("max", None),
                                "kda_chunk_decay_min": ("max", None)}


def kinds(conf: Dict) -> Tuple[Tuple[str, str], ...]:
    """(mixer, FFN) of the layers that are run, in order."""
    kda = conf["linear_attn_config"]["kda_layers"]
    return tuple(("kda" if i + 1 in kda else "latent",
                  "dense" if i < conf["first_k_dense_replace"] else "moe")
                 for i in range(conf["num_hidden_layers"]))


def kda_recurrence(q, k, v, g, beta):
    """``S' = Diag(exp(g_t)) S_(t-1)``, ``S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T``, ``o_t = S_t^T q_t``, a token at a time.  ``q``, ``k``, ``g``
    ``(rows, seq, heads, key_dim)``, ``v (rows, seq, heads, value_dim)``,
    ``beta (rows, seq, heads)``; the state is keys x values."""
    def token(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., :, None] * state
        held = jnp.sum(state * k_t[..., :, None], -2)          # S'^T k
        state = state + k_t[..., :, None] * (
            beta_t[..., None] * (v_t - held))[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], -2)

    rows, _, heads, key_dim = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((rows, heads, key_dim, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def kda_mixer(x, p, *, kda_heads, kda_dim, eps):
    """What a KDA layer adds to ``x (rows, seq, d)``."""
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
    o = rms_norm(kda_recurrence(q, k, v, g, jax.nn.sigmoid(b)),
                 p["kda_gate_norm"], eps)
    o = o * jax.nn.sigmoid(heads(gate @ p["kda_g_up"]))
    return o.reshape(rows, seq, inner) @ p["kda_out"]


def latent_mixer(x, p, *, heads, nope, rope, v_dim, latent, eps):
    """What a latent-attention layer adds: q ONE projection, no rotation."""
    rows, seq, _ = x.shape
    h = rms_norm(x, p["attn_norm"], eps)
    q = (h @ p["wq"]).reshape(rows, seq, heads, nope + rope)
    down = h @ p["wkv_a"]
    kv = (rms_norm(down[..., :latent], p["kv_a_norm"], eps)
          @ p["wkv_b"]).reshape(rows, seq, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(down[..., None, latent:],
                                          (rows, seq, heads, rope))], axis=-1)
    o = causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return o.reshape(rows, seq, heads * v_dim) @ p["wo"]


_MIXER_KW = {"kda": ("kda_heads", "kda_dim", "eps"),
             "latent": ("heads", "nope", "rope", "v_dim", "latent", "eps")}
_STATIC = ("kinds", "kda_heads", "kda_dim", "heads", "nope", "rope", "v_dim",
           "latent", "eps", "k", "factor", "first")
_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time


def _one_layer(x, kind, stack, place, kw):
    """One layer of ``kind`` on the stream ``x (rows, seq, d)``; also the
    experts its tokens chose ``(T, k)``, None of a dense one."""
    mixer, ffn = kind
    p = {name: a[place] if name in _BIG and ffn == "moe"
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    mix = kda_mixer if mixer == "kda" else latent_mixer
    x = x + mix(x, p, **{name: kw[name] for name in _MIXER_KW[mixer]})
    if ffn == "dense":
        return x + swiglu(rms_norm(x, p["mlp_norm"], kw["eps"]),
                          p["w_gate"], p["w_up"], p["w_down"]), None
    y, experts = expert_ffn(x, p, k=kw["k"], factor=kw["factor"],
                            first=kw["first"], eps=kw["eps"])
    return x + y, experts


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on the float32 stream ``x
    (rows, seq, d)``, whatever its kind; ``layers`` the program's stacks;
    ``kw`` is ``layer_kwargs``'.  Index 0, which ``rehearse_compile.py``
    compiles, is the KDA layer with the dense FFN."""
    kind, stack, place = locate(kw["kinds"], layers)[index]
    return _one_layer(x, kind, stack, place, kw)[0]


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    if conf["q_lora_rank"] or not conf["mla_use_nope"] \
            or conf["num_expert_group"] != 1:
        raise NotImplementedError(
            "a q rank, rotated latent attention or group-limited routing")
    linear = conf["linear_attn_config"]
    return dict(
        kinds=kinds(conf), kda_heads=linear["num_heads"],
        kda_dim=linear["head_dim"], heads=conf["num_attention_heads"],
        nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
        v_dim=conf["v_head_dim"], latent=conf["kv_lora_rank"],
        eps=float(conf["rms_norm_eps"]), k=conf["num_experts_per_token"],
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
    seq)``, ``experts`` (a layer that has them: ``(T, k)``) and
    ``moe_held_share`` (the choices that name a held expert over all of
    them, the mean over those layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen, held = [], 0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, kind, stack, place, **kw)
            if experts is not None:
                chosen.append(experts)
                held = stack["w_gate"].shape[1]
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
    first = kw["first"]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e in chosen) / max(len(chosen), 1)
    nll = jnp.mean(token_nll)
    return {"loss": nll, "total": nll, "token_nll": token_nll,
            "experts": chosen, "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: mean next-token cross-entropy."""
    return loss_parts(params, tokens, conf)["total"]
