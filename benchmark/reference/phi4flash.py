"""Plain reference of the Phi-4-mini-flash decoder (``model_type``
``phi4flash``: ``config.json``, ``configuration_phi4flash.py`` and
``modeling_phi4flash.py`` of huggingface.co/microsoft/
Phi-4-mini-flash-reasoning; the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with the differential attention of arXiv:2410.05258 and
Mamba-1's selective scan, arXiv:2312.00752).

WHICH LAYER IS WHAT follows from the depth ``L = num_hidden_layers`` (a
multiple of 4) and ``mb_per_layer`` 2 alone (``kinds``), ``i`` from 0: even
``i <= L/2`` a Mamba layer; odd ``i < L/2`` a windowed attention layer; ``i =
L/2 + 1`` the FULL attention layer, whose k and v are the shared ones; even
``i >= L/2 + 2`` a gated memory unit on the memory of layer ``L/2``; odd ``i
>= L/2 + 3`` a cross layer over the shared k and v.  Published, ``L`` = 32:
M W x 8, M F, then G C x 7.  A configuration file that cuts the depth applies
the rule to ITS depth (at 8: M W M W | M F | G C).

Every layer is ``x = x + mixer(LN1(x)); x = x + MLP(LN2(x))``, LN a
LayerNorm with weight and bias at ``layer_norm_eps``; one last LayerNorm;
the head is the embedding table.  With ``h = LN1(x)``:

- MLP: ``y = (silu(h2 W_gate) * (h2 W_up)) W_down``, no bias.
- Mamba-1 (inner = ``mamba_expand`` x hidden, state N = ``mamba_d_state``,
  R = ``mamba_dt_rank``, "auto": ceil(hidden / 16)): ``[xs | z] = h W_in``;
  ``xc = silu(conv(xs) + b_conv)``, depthwise, causal, ``mamba_d_conv`` taps,
  zeros before the sequence; ``[dr | B | C] = xc W_x``; ``dt = softplus(dr
  W_dt + b_dt)``; ``A = -exp(A_log)``; for every channel c and state index
  n ``H_t[c, n] = exp(dt_t[c] A[c, n]) H_(t-1)[c, n] + dt_t[c] B_t[n]
  xc_t[c]`` from ``H = 0``; ``m_t[c] = sum_n C_t[n] H_t[c, n] + D[c]
  xc_t[c]``; ``y = (m silu(z)) W_out``.  ``m`` of layer L/2 — BEFORE the
  gate — is the memory the units read.
- gated memory unit: ``y = (m silu(h W_1)) W_2``.
- differential attention (windowed, full, cross alike): ``q = h W_q + b_q``,
  ``k``, ``v`` likewise (a cross layer makes q alone and takes k, v as the
  full layer made them); no rotation; heads in interleaved pairs: q pair p
  is heads (2p, 2p + 1), its KV pair ``j = p // (q pairs / KV pairs)`` heads
  (2j, 2j + 1) of k and ``V = [v[2j] | v[2j + 1]]``; ``a1 = softmax(q1 k1^T
  / sqrt(d_head) + mask) V``, ``a2`` likewise of the second heads; ``lambda
  = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
  0.6 exp(-0.3 i)`` with i the layer's index in the model that is run; ``o_p
  = RMSNorm(a1 - lambda a2; one weight of 2 d_head, layer_norm_eps) (1 -
  lambda_init)``; the pairs side by side through ``W_o`` with its bias.
  ``mask``: causal; in a windowed layer a query reads itself and the
  ``sliding_window - 1`` keys before it.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The recurrence runs ONE TOKEN AT A TIME (``lax.scan`` over
positions, one ``(inner, N)`` state its carry): no chunk, no cumulative
product, nothing of the forms under test (``ray_tpu/ops/ssm.py``).  Each
softmax is over a materialised masked score matrix, ``Q_BLOCK`` queries at a
time (``lax.map``: as a Python loop the chip's compiler keeps every block's
scores alive).  It reads the PROGRAM'S parameters as they lie — ``embed (V,
d)``, ``final_norm``, ``final_norm_bias``, no ``lm_head``, and ``layers``: a
tuple of stacks, one a run of layers of one kind — and upcasts one layer at a
time; the head and each position's loss ``HEAD_BLOCK`` positions at a time.

Departures from the published implementation, each stated:

- the published mixers hold q, k and v in ONE matrix (``Wqkv``) and the MLP
  gate and up in one (``fc1``, the gate first); the program holds them
  apart, which random weights cannot tell.
- which heads pair (interleaved) and which KV pair serves which q pairs are
  the public code's as the issue recalls it; with one set of weights in
  program and reference, neither speed nor this check can tell another
  pairing apart.
- ``lambda_init`` is by the layer's index in the model THAT IS RUN.
- ``embd_pdrop`` / ``resid_pdrop`` are 0 as published and do nothing.
- no padding mask, no cache, no decode path: a training step on whole
  sequences.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``layer(x, layers, index,
...)`` is layer ``index`` of the model where that layer reads nothing of an
earlier one (index 0, which ``rehearse_compile.py`` compiles, is a Mamba
layer).  ``STEP_METRICS`` asks the window for ``s6_state_absmax`` (the
largest ``|H|`` at a chunk's end over the Mamba layers, the window's
maximum), ``diff_lambda`` (the attention layers' mean lambda, the window's
maximum: a number the seed fixes, which a program that leaves lambda out
reads differently) and the windowed layers' two shares, each held to no
value.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (  # noqa: F401
    LOSS_RTOL, loss_rtol, rms_norm)

Q_BLOCK = 512
HEAD_BLOCK = 2048
STEP_METRICS: Dict[str, Any] = {
    "s6_state_absmax": ("max", None), "diff_lambda": ("max", None),
    "attn_window_executed_share": ("max", None),
    "attn_window_masked_tile_share": ("max", None)}

MAMBA, WINDOWED, FULL, UNIT, CROSS = "M", "W", "F", "G", "C"


def kinds(depth: int) -> str:
    """A character a layer of a model of ``depth`` layers."""
    half = depth // 2
    return "".join(
        (MAMBA if i <= half else UNIT) if i % 2 == 0
        else WINDOWED if i < half else FULL if i == half + 1 else CROSS
        for i in range(depth))


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _upcast(stack, place):
    return jax.tree.map(lambda a: a[place].astype(jnp.float32), stack)


def _mlp(x, p, eps):
    h = layer_norm(x, p["mlp_norm"], p["mlp_norm_bias"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def recurrence(x, dt, a, b, c, d):
    """The selective scan a token at a time.  ``x``, ``dt (rows, seq,
    channels)``; ``a (channels, n)``; ``b``, ``c (rows, seq, n)``; ``d
    (channels,)``.  Returns ``m`` like ``x``."""
    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1) + d * x_t

    _, m = jax.lax.scan(
        token, jnp.zeros((x.shape[0], *a.shape), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(m, 0, 1)


@functools.partial(jax.jit, static_argnames=("state", "eps"))
def mamba_layer(x, stack, place, *, state, eps):
    """One Mamba-1 layer and its MLP on float32 ``x (rows, seq, d)``:
    ``(x, m)``, ``m`` the scan's output with ``D x``, before the gate."""
    p = _upcast(stack, place)
    inner = p["s6_out"].shape[0]
    rank = p["s6_dt"].shape[0]
    proj = layer_norm(x, p["s6_norm"], p["s6_norm_bias"], eps) @ p["s6_in"]
    xs, z = proj[..., :inner], proj[..., inner:]
    width = p["s6_conv_w"].shape[0]
    xc = jax.nn.silu(p["s6_conv_b"] + jax.lax.conv_general_dilated(
        xs, p["s6_conv_w"][:, None, :], window_strides=(1,),
        padding=[(width - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=inner, precision=jax.lax.Precision.HIGHEST))
    low = xc @ p["s6_x"]
    dr, b, c = (low[..., :rank], low[..., rank:rank + state],
                low[..., rank + state:])
    dt = jax.nn.softplus(dr @ p["s6_dt"] + p["s6_dt_bias"])
    m = recurrence(xc, dt, -jnp.exp(p["s6_A_log"]), b, c, p["s6_D"])
    return _mlp(x + (m * jax.nn.silu(z)) @ p["s6_out"], p, eps), m


@functools.partial(jax.jit, static_argnames=("eps",))
def unit_layer(x, m, stack, place, *, eps):
    """One gated memory unit on the memory ``m`` and its MLP."""
    p = _upcast(stack, place)
    h = layer_norm(x, p["gmu_norm"], p["gmu_norm_bias"], eps)
    return _mlp(x + (m * jax.nn.silu(h @ p["gmu_in"])) @ p["gmu_out"], p, eps)


def masked_attention(q, k, v, window):
    """``q (rows, seq, heads, d)`` over ``k (rows, seq, heads, d)``, ``v
    (rows, seq, heads, dv)``, head by head: softmax of ``q k^T / sqrt(d)``
    over the keys at or before each query and, with a ``window``, no
    further back than ``window - 1`` — a block of queries at a time."""
    rows, seq, heads, d = q.shape
    block = min(Q_BLOCK, seq)
    pad = -seq % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        rows, -1, block, heads, d)
    key_pos = jnp.arange(seq)

    def one(at):
        start, qs = at
        scores = jnp.einsum("bqhd,bshd->bhqs", qs, k) / math.sqrt(d)
        query_pos = start + jnp.arange(block)
        seen = key_pos[None, :] <= query_pos[:, None]
        if window:
            seen &= query_pos[:, None] - key_pos[None, :] < window
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (jnp.arange(0, seq + pad, block),
                            jnp.moveaxis(qb, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(rows, seq + pad, heads, -1)[:, :seq]


def keys_and_values(h, p, kv_heads):
    """``(k (rows, seq, kv_heads, d), v likewise)`` of a layer that makes
    its own."""
    rows, seq, _ = h.shape
    return ((h @ p["wk"] + p["bk"]).reshape(rows, seq, kv_heads, -1),
            (h @ p["wv"] + p["bv"]).reshape(rows, seq, kv_heads, -1))


def differential(h, k, v, p, *, index, heads, window, eps):
    """What a differential-attention mixer adds, from its normed input
    ``h`` and the keys and values ``k``, ``v (rows, seq, kv_heads, d)``."""
    rows, seq, _ = h.shape
    kv_heads, d = k.shape[2], k.shape[3]
    q = (h @ p["wq"] + p["bq"]).reshape(rows, seq, heads // 2, 2, d)
    q1, q2 = q[:, :, :, 0], q[:, :, :, 1]
    group = heads // kv_heads       # q pairs a KV pair serves
    pairs = k.reshape(rows, seq, kv_heads // 2, 2, d)
    k1, k2 = (jnp.repeat(pairs[:, :, :, i], group, axis=2) for i in (0, 1))
    doubled = jnp.repeat(v.reshape(rows, seq, kv_heads // 2, 2 * d), group,
                         axis=2)
    a1 = masked_attention(q1, k1, doubled, window)
    a2 = masked_attention(q2, k2, doubled, window)
    start = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    o = rms_norm(a1 - lam * a2, p["diff_norm"], eps) * (1.0 - start)
    return o.reshape(rows, seq, -1) @ p["wo"] + p["bo"]


@functools.partial(jax.jit, static_argnames=(
    "index", "heads", "kv_heads", "window", "eps"))
def attention_layer(x, stack, place, *, index, heads, kv_heads, window, eps):
    """One differential-attention layer that makes its own keys and values
    (``window`` 0: every earlier token) and its MLP: ``(x, k, v)``."""
    p = _upcast(stack, place)
    h = layer_norm(x, p["attn_norm"], p["attn_norm_bias"], eps)
    k, v = keys_and_values(h, p, kv_heads)
    y = differential(h, k, v, p, index=index, heads=heads, window=window,
                     eps=eps)
    return _mlp(x + y, p, eps), k, v


@functools.partial(jax.jit, static_argnames=("index", "heads", "eps"))
def cross_layer(x, k, v, stack, place, *, index, heads, eps):
    """One cross layer over the full layer's ``k``, ``v`` and its MLP."""
    p = _upcast(stack, place)
    h = layer_norm(x, p["attn_norm"], p["attn_norm_bias"], eps)
    y = differential(h, k, v, p, index=index, heads=heads, window=0, eps=eps)
    return _mlp(x + y, p, eps)


def locate(depth: int, layers) -> Tuple[Tuple[str, Any, int], ...]:
    """Layer -> (kind, the stack of its run, its place in that stack)."""
    stacks = layers if isinstance(layers, (tuple, list)) else (layers,)
    order, out, run = kinds(depth), [], -1
    for i, kind in enumerate(order):
        if i == 0 or kind != order[i - 1]:
            run, place = run + 1, 0
        out.append((kind, stacks[run], place))
        place += 1
    return tuple(out)


def _apply(x, memory, shared, index, located, kw):
    """Layer ``index`` on ``x``: ``(x, the memory, the shared k and v)`` as
    they stand after it."""
    kind, stack, place = located
    eps, heads = kw["eps"], kw["heads"]
    if kind == MAMBA:
        x, memory = mamba_layer(x, stack, place, state=kw["state"], eps=eps)
    elif kind == UNIT:
        x = unit_layer(x, memory, stack, place, eps=eps)
    elif kind == CROSS:
        x = cross_layer(x, *shared, stack, place, index=index, heads=heads,
                        eps=eps)
    else:
        x, *made = attention_layer(
            x, stack, place, index=index, heads=heads,
            kv_heads=kw["kv_heads"], eps=eps,
            window=kw["window"] if kind == WINDOWED else 0)
        if kind == FULL:
            shared = tuple(made)
    return x, memory, shared


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=(
    "depth", "heads", "kv_heads", "window", "state", "eps"))
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``, for a layer that reads nothing of an earlier one."""
    return _apply(x, None, None, index,
                  locate(kw["depth"], layers)[index], kw)[0]


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(depth=conf["num_hidden_layers"],
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                window=conf["sliding_window"], state=conf["mamba_d_state"],
                eps=float(conf["layer_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _tied_head_nll(x, final_norm, final_bias, embed, targets, *, eps):
    """The last LayerNorm, the tied head and each position's next-token
    loss ``(rows, seq)``, ``HEAD_BLOCK`` positions at a time."""
    rows, seq, d = x.shape
    table = embed.astype(jnp.float32)
    h = layer_norm(x, final_norm.astype(jnp.float32),
                   final_bias.astype(jnp.float32), eps).reshape(-1, d)
    wanted = targets.reshape(-1)
    out = []
    for start in range(0, rows * seq, HEAD_BLOCK):
        logp = jax.nn.log_softmax(h[start:start + HEAD_BLOCK] @ table.T,
                                  axis=-1)
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
        memory = shared = None
        for index, located in enumerate(locate(kw["depth"],
                                               params["layers"])):
            x, memory, shared = _apply(x, memory, shared, index, located, kw)
        token_nll = _tied_head_nll(
            x, params["final_norm"], params["final_norm_bias"],
            params["embed"], targets, eps=kw["eps"])
    nll = jnp.mean(token_nll)
    return {"total": nll, "loss": nll, "token_nll": token_nll}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (rows, seq + 1)."""
    return loss_parts(params, tokens, conf)["total"]
