"""Plain reference of the Granite 4.0-H decoder (``model_type``
``granitemoehybrid``; ``config.json`` and ``modeling_granitemoehybrid.py``
of huggingface.co/ibm-granite/granite-4.0-h-micro): Mamba-2 layers
(arXiv:2405.21060) with a softmax-attention layer where ``layer_types``
says ``attention``, a SwiGLU MLP in every layer, one tied table.

The model is the FIRST ``num_hidden_layers`` entries of ``layer_types``
(a configuration file that cuts the depth keeps the published list).  With
``e`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier``, every
norm an RMSNorm with a learned weight and ``rms_norm_eps``:

- ``x = embed[tokens] * e``.  Each layer: ``x = x + r * mixer(norm(x))``,
  then ``x = x + r * mlp(norm(x))``, ``mlp(h) = (silu(h W_gate) *
  (h W_up)) W_down``.  End: ``logits = norm(x) @ embed^T /
  logits_scaling``; the loss is the mean next-token cross-entropy.
- attention mixer: q, k, v projections without bias, NO rotary embedding
  (``position_embedding_type`` ``nope``), causal softmax of ``q k^T *
  attention_multiplier`` (a given number: 1/64 at head size 64, not
  1/sqrt(64)), query head ``j`` reads KV head ``j // (heads / kv_heads)``,
  output projection.
- Mamba-2 mixer, ``h = norm(x)``: ``[z | xBC | dt] = h W_in`` (no bias;
  widths ``heads * d_head``, ``heads * d_head + 2 * groups * d_state``,
  ``heads``); ``xBC = silu(conv(xBC))``, a causal depthwise convolution of
  width ``mamba_d_conv`` WITH bias whose last tap meets the current token;
  ``xBC`` splits into ``x`` (heads x d_head), ``B`` and ``C`` (groups x
  d_state, a group shared by heads / groups heads); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` a head; per head the state ``H_t =
  exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`` (d_head x d_state) and ``y_t = H_t
  C_t + D x_t``; ``y = norm(y * silu(z))``: the GATE FIRST, then one
  RMSNorm over the whole inner width (``mamba_n_groups`` 1); output
  projection without bias.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The state recurrence runs ONE TOKEN AT A TIME (``lax.scan``
over positions, the state its carry): no chunk, no decay matrix, no
cumulative sum — nothing of the chunked algorithm under test
(``ray_tpu/ops/ssm.py``).  The convolution is ``lax.conv_general_dilated``
with one feature group a channel, where the program adds shifted copies.
It reads the PROGRAM'S parameters as they lie — ``embed (V, d)``,
``final_norm``, no ``lm_head``, and ``layers``: one stack a run of layers
of one kind, a tuple of them in the model's order (the stack itself where
the model has one kind); a Mamba stack holds ``ssm_norm``, ``ssm_in (L, d,
[z|xBC|dt])``, ``conv_w (L, width, channels)``, ``conv_b``, ``dt_bias``,
``A_log``, ``D (L, heads)``, ``gate_norm (L, inner)``, ``ssm_out (L,
inner, d)``, an attention stack ``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``, both ``mlp_norm``, ``w_gate``, ``w_up``, ``w_down`` — and upcasts
one layer at a time.  The head and each position's loss are computed for
``HEAD_BLOCK`` positions at a time: float32 logits of 8192 x 100352 are
3.3 GB, beside a resident train state.

Departures from the published implementation, each stated:

- the published MLP holds gate and up in ONE matrix (``input_linear``,
  split in halves); the program holds two, which random weights cannot
  tell apart.
- ``time_step_limit`` (a clamp of ``dt``) is (0, inf) in the published
  configuration and is left out; ``mamba_proj_bias`` and ``attention_bias``
  are false there, ``mamba_conv_bias`` true: there is no switch for them.
- ``A_log``, ``dt_bias`` and ``D`` are read in the dtype the program keeps
  them in (the configuration's ``param_dtype``); the published checkpoint
  keeps them in float32.
- the published code computes the recurrence in chunks (its "SSD" path);
  this file the recurrence itself, which is what the chunks must equal.
- attention is computed for ``Q_BLOCK`` query positions at a time against
  the whole prefix, only to bound the score matrix's memory.
- no padding mask, no cache, no multi-token ``cache_position`` logic: a
  training step on whole sequences.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``layer(x, layers, index,
...)`` is layer ``index`` of the model, whatever its kind (``index`` is
static); index 0, which ``rehearse_compile.py`` compiles, is a Mamba layer.
The tolerance of the mean is the dense decoder's (``loss_rtol``); the
limit of the per-token comparison is the configuration file's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (  # noqa: F401
    LOSS_RTOL, loss_rtol, rms_norm)

Q_BLOCK = 1024
HEAD_BLOCK = 2048
STEP_METRICS: Dict[str, Any] = {}  # the step reports nothing to hold


def locate(kinds, layers) -> List[Tuple[str, Any, int]]:
    """Layer -> (kind, the stack of its run, its place in that stack), for
    the model's ``kinds`` in order and the program's ``layers``."""
    stacks = layers if isinstance(layers, (tuple, list)) else (layers,)
    out, run = [], -1
    for i, kind in enumerate(kinds):
        if i == 0 or kind != kinds[i - 1]:
            run, place = run + 1, 0
        out.append((kind, stacks[run], place))
        place += 1
    return out


def _upcast(stack, place):
    return jax.tree.map(lambda a: a[place].astype(jnp.float32), stack)


def _mlp(x, p, eps, residual):
    h = rms_norm(x, p["mlp_norm"], eps)
    return x + residual * (
        (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"])


def causal_attention(q, k, v, scale):
    """q: (rows, seq, kv_heads, group, d_head); k, v: (rows, seq,
    kv_heads, d_head).  Softmax of ``q k^T * scale`` over the keys at or
    before each query."""
    seq = q.shape[1]
    key_pos = jnp.arange(seq)
    out = []
    for start in range(0, seq, Q_BLOCK):
        qb = q[:, start:start + Q_BLOCK]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) * scale
        query_pos = start + jnp.arange(qb.shape[1])
        visible = key_pos[None, :] <= query_pos[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "eps", "residual"))
def attention_layer(x, stack, place, *, heads, kv_heads, scale, eps,
                    residual):
    """One attention layer (no position signal) and its MLP on float32
    ``x (rows, seq, d)``, with layer ``place`` of ``stack`` upcast."""
    p = _upcast(stack, place)
    rows, seq, _ = x.shape
    d_head = p["wq"].shape[-1] // heads
    h = rms_norm(x, p["attn_norm"], eps)
    q = (h @ p["wq"]).reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    k = (h @ p["wk"]).reshape(rows, seq, kv_heads, d_head)
    v = (h @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    o = causal_attention(q, k, v, scale).reshape(rows, seq, heads * d_head)
    return _mlp(x + residual * (o @ p["wo"]), p, eps, residual)


def recurrence(x, dt, a, b, c, d):
    """``H_t = exp(dt_t a) H_(t-1) + dt_t x_t b_t^T``, ``y_t = H_t c_t + d
    x_t``, a token at a time.  ``x (rows, seq, heads, d_head)``, ``dt
    (rows, seq, heads)``, ``a``, ``d (heads,)``, ``b``, ``c (rows, seq,
    heads, d_state)``."""
    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], -1) + d[:, None] * x_t

    rows, _, heads, d_head = x.shape
    _, y = jax.lax.scan(
        token, jnp.zeros((rows, heads, d_head, b.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@functools.partial(jax.jit, static_argnames=(
    "ssm_heads", "d_head", "d_state", "groups", "eps", "residual"))
def mamba_layer(x, stack, place, *, ssm_heads, d_head, d_state, groups, eps,
                residual):
    """One Mamba-2 layer and its MLP on float32 ``x (rows, seq, d)``, with
    layer ``place`` of ``stack`` upcast."""
    p = _upcast(stack, place)
    rows, seq, _ = x.shape
    inner, gn = ssm_heads * d_head, groups * d_state
    proj = rms_norm(x, p["ssm_norm"], eps) @ p["ssm_in"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    width, channels = p["conv_w"].shape
    xbc = jax.nn.silu(p["conv_b"] + jax.lax.conv_general_dilated(
        xbc, p["conv_w"][:, None, :], window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision=jax.lax.Precision.HIGHEST))
    xs = xbc[..., :inner].reshape(rows, seq, ssm_heads, d_head)
    per_group = ssm_heads // groups
    b, c = (jnp.repeat(t.reshape(rows, seq, groups, d_state), per_group, 2)
            for t in (xbc[..., inner:inner + gn], xbc[..., inner + gn:]))
    y = recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]), b, c, p["D"])
    y = rms_norm(y.reshape(rows, seq, inner) * jax.nn.silu(z),
                 p["gate_norm"], eps)
    return _mlp(x + residual * (y @ p["ssm_out"]), p, eps, residual)


_LAYERS = {"attention": (attention_layer, ("heads", "kv_heads", "scale")),
           "mamba": (mamba_layer, ("ssm_heads", "d_head", "d_state",
                                   "groups"))}
_STATIC = ("kinds", "eps", "residual") + tuple(
    name for _, names in _LAYERS.values() for name in names)


def _apply(x, located, kw):
    kind, stack, place = located
    fn, names = _LAYERS[kind]
    return fn(x, stack, place, **{k: kw[k] for k in ("eps", "residual")
                                  + names})


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``, whatever its kind; ``kw`` is ``layer_kwargs``'."""
    return _apply(x, locate(kw["kinds"], layers)[index], kw)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names): the mixers of the layers that are
    run, in order, and both mixers' sizes."""
    return dict(
        kinds=tuple(conf["layer_types"][:conf["num_hidden_layers"]]),
        eps=float(conf["rms_norm_eps"]),
        residual=float(conf["residual_multiplier"]),
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        scale=float(conf["attention_multiplier"]),
        ssm_heads=conf["mamba_n_heads"], d_head=conf["mamba_d_head"],
        d_state=conf["mamba_d_state"], groups=conf["mamba_n_groups"])


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _tied_head_nll(x, final_norm, embed, targets, *, eps, scaling):
    """Final norm, the tied head (``embed^T``, logits divided by
    ``scaling``) and each position's next-token loss ``(rows, seq)``,
    ``HEAD_BLOCK`` positions at a time."""
    rows, seq, d = x.shape
    table = embed.astype(jnp.float32)
    h = rms_norm(x, final_norm.astype(jnp.float32), eps).reshape(-1, d)
    wanted = targets.reshape(-1)
    out = []
    for start in range(0, rows * seq, HEAD_BLOCK):
        logp = jax.nn.log_softmax(
            h[start:start + HEAD_BLOCK] @ table.T / scaling, axis=-1)
        out.append(-jnp.take_along_axis(
            logp, wanted[start:start + HEAD_BLOCK, None], axis=-1)[:, 0])
    return jnp.concatenate(out).reshape(rows, seq)


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, jax.Array]:
    """Of ``tokens`` (rows, seq + 1) under the configuration file ``conf``:
    ``token_nll`` and its mean, which is the training loss."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(
            jnp.float32) * conf["embedding_multiplier"]
        kw = layer_kwargs(conf)
        for located in locate(kw["kinds"], params["layers"]):
            x = _apply(x, located, kw)
        token_nll = _tied_head_nll(
            x, params["final_norm"], params["embed"], targets,
            eps=float(conf["rms_norm_eps"]),
            scaling=float(conf["logits_scaling"]))
    nll = jnp.mean(token_nll)
    return {"total": nll, "loss": nll, "token_nll": token_nll}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (rows, seq + 1)."""
    return loss_parts(params, tokens, conf)["total"]
