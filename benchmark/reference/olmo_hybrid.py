"""Plain reference of the Olmo-Hybrid decoder (``model_type``
``olmo_hybrid``; ``config.json`` of huggingface.co/allenai/Olmo-Hybrid-7B):
gated delta-rule linear-attention layers (Gated Delta Networks,
arXiv:2412.06464; negative eigenvalues, arXiv:2411.12537) with a softmax
attention layer where ``layer_types`` says ``full_attention``, a SwiGLU
MLP in every layer, an untied head.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types`` (a
configuration file that cuts the depth keeps the published list).  Every
norm is an RMSNorm with a learned weight and ``rms_norm_eps``, and the block
is the OLMo 2 / 3 family's, which norms what a block ADDS:

- ``x = embed[tokens]``.  Each layer: ``x = x + norm(mixer(x))``, then ``x
  = x + norm(mlp(x))``, ``mlp(h) = (silu(h W_gate) * (h W_up)) W_down``.
  End: ``logits = norm(x) @ lm_head``; the loss is the mean next-token
  cross-entropy.
- ``full_attention`` mixer: q, k, v projections of ``x`` without bias, an
  RMSNorm over the WHOLE q and the whole k projection (before the split
  into heads), NO rotary embedding (``rope_parameters.rope_theta`` null),
  causal softmax of ``q k^T / sqrt(head_dim)`` over ``num_attention_heads``
  heads (``num_key_value_heads`` equal: no grouping), output projection.
- ``linear_attention`` mixer, per head ``h`` of ``linear_num_value_heads``
  (= ``linear_num_key_heads``): ``[q | k | v | gate | a | b] = x W_in``
  (widths heads x ``linear_key_head_dim`` twice, heads x
  ``linear_value_head_dim`` twice, heads, heads); ``[q | k | v]`` through a
  causal depthwise convolution of width ``linear_conv_kernel_dim`` WITHOUT
  bias whose last tap meets the current token, then SiLU; ``q = q / |q| *
  key_dim ** -0.5`` and ``k = k / |k|`` per head (``|.|`` as the published
  kernels' ``l2norm`` has it: ``sqrt(sum of squares + 1e-6)``); ``beta = 2
  sigmoid(b)`` (the 2 is ``linear_allow_neg_eigval``: the transition's
  eigenvalue along ``k`` is ``1 - beta`` in (-1, 1)); log-decay ``g =
  -exp(A_log) softplus(a + dt_bias)``; the state ``S_t = exp(g_t) S_(t-1)
  (I - beta_t k_t k_t^T) + beta_t v_t k_t^T`` (value size x key size) and
  ``o_t = S_t q_t``; ``o = norm_h(o) * silu(gate)``, the norm over each
  head's values with ONE weight of ``linear_value_head_dim``; output
  projection.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The state recurrence runs ONE TOKEN AT A TIME (``lax.scan``
over positions, the state its carry): no chunk, no triangular inverse, no
cumulative sum — nothing of the chunked algorithm under test
(``ray_tpu/ops/delta.py``).  The convolution is
``lax.conv_general_dilated`` with one feature group a channel, where the
program adds shifted copies.  It reads the PROGRAM'S parameters as they lie
— ``embed (V, d)``, ``final_norm``, ``lm_head (d, V)`` and ``layers``: one
stack a run of layers of one kind, a tuple of them in the model's order; a
linear stack holds ``gdn_norm``, ``gdn_in (L, d, [q|k|v|gate|a|b])``,
``gdn_conv_w (L, width, channels)``, ``gdn_dt_bias``, ``gdn_A_log (L,
heads)``, ``gdn_gate_norm (L, value_dim)``, ``gdn_out (L, inner, d)``, a
full stack ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``,
``k_norm``, both ``mlp_norm``, ``w_gate``, ``w_up``, ``w_down`` — and
upcasts one layer at a time.  The head and each position's loss are
computed for ``HEAD_BLOCK`` positions at a time.

What the public ``config.json`` does not state and this file (with the
configuration's ``assumed``) sets, each the family's convention: the norm
on a block's OUTPUT and the RMSNorm over the whole q and k projections
(OLMo 2, arXiv:2501.00656 section 3.1), no position signal in the softmax
layers (``rope_theta`` null), ``head_dim = hidden_size /
num_attention_heads``, a convolution without bias and the mixer's
``A_log`` / ``dt_bias`` parametrisation of the decay (the Gated DeltaNet
reference code, flash-linear-attention).  Further departures:

- ``A_log`` and ``dt_bias`` are read in the dtype the program keeps them in
  (the configuration's ``param_dtype``).
- the published code computes the rule in chunks; this file the recurrence
  itself, which is what the chunks must equal.
- attention is computed for ``Q_BLOCK`` query positions at a time against
  the whole prefix, only to bound the score matrix's memory.
- no padding mask, no cache: a training step on whole sequences.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``layer(x, layers, index,
...)`` is layer ``index`` of the model, whatever its kind (``index`` is
static); index 0, which ``rehearse_compile.py`` compiles, is a linear
layer.  ``STEP_METRICS`` asks the window for the step's
``gdn_state_absmax`` — the largest ``|S|`` at a chunk's end over the linear
layers — kept as the window's maximum and held to no value: with ``beta``
up to 2 and bfloat16 operands it is the first number to read when the
per-token comparison drifts.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (  # noqa: F401
    LOSS_RTOL, loss_rtol, rms_norm)
from benchmark.reference.granite_hybrid import (
    _upcast, causal_attention, locate)

HEAD_BLOCK = 2048
L2_EPS = 1e-6
STEP_METRICS: Dict[str, Any] = {"gdn_state_absmax": ("max", None)}


def _mlp(x, p, eps):
    y = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return x + rms_norm(y, p["mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def full_layer(x, stack, place, *, heads, eps):
    """One softmax-attention layer (no position signal) and its MLP on
    float32 ``x (rows, seq, d)``, with layer ``place`` of ``stack``
    upcast."""
    p = _upcast(stack, place)
    rows, seq, _ = x.shape
    d_head = p["wq"].shape[-1] // heads
    q = rms_norm(x @ p["wq"], p["q_norm"], eps).reshape(
        rows, seq, heads, 1, d_head)
    k = rms_norm(x @ p["wk"], p["k_norm"], eps).reshape(
        rows, seq, heads, d_head)
    v = (x @ p["wv"]).reshape(rows, seq, heads, d_head)
    o = causal_attention(q, k, v, d_head ** -0.5).reshape(rows, seq, -1)
    return _mlp(x + rms_norm(o @ p["wo"], p["attn_norm"], eps), p, eps)


def delta_recurrence(q, k, v, g, beta):
    """``S_t = exp(g_t) S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
    ``o_t = S_t q_t``, a token at a time.  ``q``, ``k`` ``(rows, seq, heads,
    key_dim)``, ``v (rows, seq, heads, value_dim)``, ``g``, ``beta`` ``(rows,
    seq, heads)``."""
    def token(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        held = jnp.sum(state * k_t[..., None, :], -1)        # S k
        state = jnp.exp(g_t)[..., None, None] * (
            state - (beta_t[..., None] * held)[..., :, None]
            * k_t[..., None, :]) + (
                beta_t[..., None] * v_t)[..., :, None] * k_t[..., None, :]
        return state, jnp.sum(state * q_t[..., None, :], -1)

    rows, _, heads, key_dim = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((rows, heads, v.shape[-1], key_dim), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=(
    "gdn_heads", "key_dim", "value_dim", "neg_eigval", "eps"))
def linear_layer(x, stack, place, *, gdn_heads, key_dim, value_dim,
                 neg_eigval, eps):
    """One gated delta-rule layer and its MLP on float32 ``x (rows, seq,
    d)``, with layer ``place`` of ``stack`` upcast."""
    p = _upcast(stack, place)
    rows, seq, _ = x.shape
    keys, values = gdn_heads * key_dim, gdn_heads * value_dim
    proj = x @ p["gdn_in"]
    qkv, gate, a, b = (proj[..., :2 * keys + values],
                       proj[..., 2 * keys + values:2 * keys + 2 * values],
                       proj[..., 2 * keys + 2 * values:-gdn_heads],
                       proj[..., -gdn_heads:])
    width, channels = p["gdn_conv_w"].shape
    qkv = jax.nn.silu(jax.lax.conv_general_dilated(  # no bias
        qkv, p["gdn_conv_w"][:, None, :], window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision=jax.lax.Precision.HIGHEST))
    q = _unit(qkv[..., :keys].reshape(rows, seq, gdn_heads, key_dim)
              ) * key_dim ** -0.5
    k = _unit(qkv[..., keys:2 * keys].reshape(rows, seq, gdn_heads, key_dim))
    v = qkv[..., 2 * keys:].reshape(rows, seq, gdn_heads, value_dim)
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(b)
    g = -jnp.exp(p["gdn_A_log"]) * jax.nn.softplus(a + p["gdn_dt_bias"])
    o = rms_norm(delta_recurrence(q, k, v, g, beta), p["gdn_gate_norm"], eps)
    o = o * jax.nn.silu(gate.reshape(rows, seq, gdn_heads, value_dim))
    y = o.reshape(rows, seq, values) @ p["gdn_out"]
    return _mlp(x + rms_norm(y, p["gdn_norm"], eps), p, eps)


_LAYERS = {"full_attention": (full_layer, ("heads",)),
           "linear_attention": (linear_layer, (
               "gdn_heads", "key_dim", "value_dim", "neg_eigval"))}
_STATIC = ("kinds", "eps") + tuple(
    name for _, names in _LAYERS.values() for name in names)


def _apply(x, located, kw):
    kind, stack, place = located
    fn, names = _LAYERS[kind]
    return fn(x, stack, place, **{k: kw[k] for k in ("eps",) + names})


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``, whatever its kind; ``kw`` is ``layer_kwargs``'."""
    return _apply(x, locate(kw["kinds"], layers)[index], kw)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names): the mixers of the layers that are
    run, in order, and both mixers' sizes."""
    if conf["linear_num_key_heads"] != conf["linear_num_value_heads"] or \
            conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise NotImplementedError(
            "key heads shared by several value or query heads")
    return dict(
        kinds=tuple(conf["layer_types"][:conf["num_hidden_layers"]]),
        eps=float(conf["rms_norm_eps"]),
        heads=conf["num_attention_heads"],
        gdn_heads=conf["linear_num_value_heads"],
        key_dim=conf["linear_key_head_dim"],
        value_dim=conf["linear_value_head_dim"],
        neg_eigval=bool(conf["linear_allow_neg_eigval"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_nll(x, final_norm, lm_head, targets, *, eps):
    """Final norm, the untied head and each position's next-token loss
    ``(rows, seq)``, ``HEAD_BLOCK`` positions at a time."""
    rows, seq, d = x.shape
    head = lm_head.astype(jnp.float32)
    h = rms_norm(x, final_norm.astype(jnp.float32), eps).reshape(-1, d)
    wanted = targets.reshape(-1)
    out = []
    for start in range(0, rows * seq, HEAD_BLOCK):
        logp = jax.nn.log_softmax(h[start:start + HEAD_BLOCK] @ head, axis=-1)
        out.append(-jnp.take_along_axis(
            logp, wanted[start:start + HEAD_BLOCK, None], axis=-1)[:, 0])
    return jnp.concatenate(out).reshape(rows, seq)


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, jax.Array]:
    """Of ``tokens`` (rows, seq + 1) under the configuration file ``conf``:
    ``token_nll`` and its mean, which is the training loss."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        kw = layer_kwargs(conf)
        for located in locate(kw["kinds"], params["layers"]):
            x = _apply(x, located, kw)
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=float(conf["rms_norm_eps"]))
    nll = jnp.mean(token_nll)
    return {"total": nll, "loss": nll, "token_nll": token_nll}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (rows, seq + 1)."""
    return loss_parts(params, tokens, conf)["total"]
