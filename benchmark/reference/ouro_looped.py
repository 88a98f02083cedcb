"""Plain reference of Ouro-2.6B (``model_type`` ``ouro``; ``config.json`` of
huggingface.co/ByteDance/Ouro-2.6B; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of layers run
``total_ut_steps`` times over the SAME weights, an exit gate and the head
read after every pass, and a loss that is the exit distribution's expected
next-token loss less ``beta`` times its entropy.

With ``n(.)`` an RMSNorm with its own learned weight and ``rms_norm_eps``:

- Embedding: ``h^(0) = E[x]``, no multiplier.
- A pass ``t = 1..T`` (``T = total_ut_steps``), the same weights at every
  ``t``: ``u = h^(t-1)``; for the layers ``l = 1..N``

      a = u + n_2l(Attn_l(n_1l(u)))
      u = a + n_4l(MLP_l(n_3l(a)))                  four norms a layer

  then ``h^(t) = n_f(u)``, the model's ONE final norm at the end of EVERY
  pass: ``h^(t)`` is what the gate and the head read AND what pass ``t + 1``
  starts from.
- ``Attn(h)``: q, k, v, o without a bias, ``heads`` of ``d_head`` (query
  head ``j`` reads KV head ``j // group``), q and k rotated over the whole
  head at ``rope_theta`` (dimension ``i`` paired with ``i + d_head / 2``),
  positions ``0..L-1`` the same at every pass; causal, scale ``d_head **
  -0.5``.  ``MLP(h) = W_down(silu(W_gate h) * W_up h)``.
- Gate and head, every pass: ``g^(t) = h^(t) w_g + b_g`` (one number a
  token), ``lambda_t = sigmoid(g^(t))``; ``z^(t) = h^(t) W_head`` (untied;
  no second norm, ``h^(t)`` is normed already).
- The exit distribution of a token (``exit_distribution``): ``S_0 = 1``;
  for ``t < T``: ``p_t = lambda_t S_(t-1)``, ``S_t = S_(t-1) (1 -
  lambda_t)``; ``p_T = S_(T-1)`` (``lambda_T`` is not read): ``sum_t p_t =
  1``.
- The loss (the paper's stage-I objective): with ``nll_i^(t) = -log
  softmax(z_i^(t))[x_(i+1)]``,

      L_i = sum_t p_(i,t) nll_i^(t) - beta H(p_i),   H(p) = -sum_t p_t log p_t

  ``loss = mean_i L_i``; the gradient flows through ``p`` and through every
  ``nll^(t)``: nothing is detached.  ``beta`` is the configuration file's
  ``looped.entropy_coef``.
- ``early_exit_threshold`` 1 exits nowhere early: the model's logits are
  ``z^(T)``, and ``token_nll`` below is the LAST pass's per-token loss —
  what the train loop's per-token row reads of the program's ``forward``.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel and no scan over passes or layers: a Python loop
over ``T`` and over ``N``, the same jitted layer called ``T x N`` times on
the same stacked parameters; the scores are written ``Q_BLOCK`` query rows
at a time against the whole prefix under ``lax.map`` (a Python loop of
blocks keeps many alive on the chip).  It reads the PROGRAM'S parameters as
they lie (``attn_norm``, ``attn_post_norm``, ``wq``, ``wk``, ``wv``, ``wo``,
``mlp_norm``, ``mlp_post_norm``, ``w_gate``, ``w_up``, ``w_down`` stacked on
a leading layer dimension; ``final_norm``, ``lm_head``, ``exit_gate (d,)``,
``exit_gate_bias ()``) and upcasts one layer at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import (  # noqa: F401 — loss_rtol: the contract
    apply_rope, loss_rtol, rms_norm, rope_tables)

Q_BLOCK = 1024
# What the window fetches with every loss (``decoder.py`` has the form):
# every step runs the file's passes; what the gate does is kept, and held to
# nothing (random weights leave it near the uniform exit).
STEP_METRICS = {"ut_steps": ("max", 4.0),
                "ut_exit_entropy": ("max", None),
                "ut_expected_steps": ("max", None)}


def causal_attention(q, k, v):
    """q: (rows, seq, kv_heads, group, d_head); k, v: (rows, seq, kv_heads,
    d_head).  Softmax over the keys at or before each query, ``Q_BLOCK``
    queries at a time."""
    rows, seq, d_head = q.shape[0], q.shape[1], q.shape[-1]
    key_pos = jnp.arange(seq)

    def some(args):
        first, q_ = args
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_, k) * d_head ** -0.5
        seen = key_pos[None, :] <= (first + jnp.arange(q_.shape[1]))[:, None]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", prob, v)

    size = min(Q_BLOCK, seq)
    out = jax.lax.map(some, (jnp.arange(0, seq, size), jnp.moveaxis(
        q.reshape(rows, seq // size, size, *q.shape[2:]), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def layer(x, layers, index, *, heads, kv_heads, theta, eps):
    """One layer on float32 ``x (rows, seq, d)`` with layer ``index`` of the
    stacked parameters upcast to float32: each half normed before AND
    after."""
    p = jax.tree.map(lambda a: a[index].astype(jnp.float32), layers)
    rows, seq, _ = x.shape
    d_head = p["wq"].shape[-1] // heads
    cos, sin = rope_tables(seq, d_head, theta)
    h = rms_norm(x, p["attn_norm"], eps)
    q = apply_rope((h @ p["wq"]).reshape(rows, seq, heads, d_head), cos, sin)
    k = apply_rope((h @ p["wk"]).reshape(rows, seq, kv_heads, d_head),
                   cos, sin)
    v = (h @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    o = causal_attention(q, k, v).reshape(rows, seq, heads * d_head)
    x = x + rms_norm(o @ p["wo"], p["attn_post_norm"], eps)
    h = rms_norm(x, p["mlp_norm"], eps)
    y = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + rms_norm(y, p["mlp_post_norm"], eps)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(u, weight, *, eps):
    """The model's ONE last norm, at the end of every pass."""
    return rms_norm(u, weight.astype(jnp.float32), eps)


@jax.jit
def _exit(h, gate, bias, lm_head, targets):
    """Of a pass's normed ``h``: the gate's logit and each position's
    next-token loss, ``(rows, seq)`` both."""
    g = h @ gate.astype(jnp.float32) + bias.astype(jnp.float32)
    logp = jax.nn.log_softmax(h @ lm_head.astype(jnp.float32), axis=-1)
    return g, -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def passes(params: Dict[str, Any], inputs: jax.Array, conf: Dict):
    """``h^(1) .. h^(T)`` of ``inputs (rows, seq)``, each ``(rows, seq,
    d)``: a Python loop over the passes and, inside it, over the layers."""
    kw, eps = layer_kwargs(conf), float(conf["rms_norm_eps"])
    h, out = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32), []
    for _ in range(conf["total_ut_steps"]):
        for i in range(conf["num_hidden_layers"]):
            h = layer(h, params["layers"], i, **kw)
        h = _final_norm(h, params["final_norm"], eps=eps)
        out.append(h)
    return out


def exit_distribution(g):
    """``p (T, ...)`` from the gate logits ``g (T, ...)``: a token exits
    after pass ``t < T`` with ``lambda_t`` of what survived the passes
    before it, and after the last with all that is left."""
    lam = jax.nn.sigmoid(g)
    survived, p = jnp.ones_like(g[0]), []
    for t in range(g.shape[0] - 1):
        p.append(lam[t] * survived)
        survived = survived * (1.0 - lam[t])
    return jnp.stack(p + [survived])


def entropy(p):
    """``H(p) = -sum_t p_t log p_t`` over the leading axis (0 log 0 = 0)."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def logits(params: Dict[str, Any], inputs: jax.Array, conf: Dict) -> jax.Array:
    """The LAST pass's logits ``(rows, seq, vocab)``: what the program's
    ``forward`` returns (the tests' use; the check never holds them)."""
    with jax.default_matmul_precision("highest"):
        return passes(params, inputs, conf)[-1] @ params["lm_head"].astype(
            jnp.float32)


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``total`` and ``loss`` (the objective), ``token_nll (rows, seq)`` (the
    LAST pass's per-token next-token loss), each pass's mean loss
    ``ut_nll_t``, ``ut_exit_entropy`` (mean ``H(p)``, nats),
    ``ut_expected_steps`` (mean ``sum_t t p_t``), ``ut_steps``, and the exit
    distribution ``p (T, rows, seq)``."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        read = [_exit(h, params["exit_gate"], params["exit_gate_bias"],
                      params["lm_head"], targets)
                for h in passes(params, inputs, conf)]
    g, nll = (jnp.stack(a) for a in zip(*read))
    return _objective(g, nll, beta=float(conf["looped"]["entropy_coef"]))


@functools.partial(jax.jit, static_argnames=("beta",))
def _objective(g, nll, *, beta):
    """``loss_parts``' dict from every pass's gate logits and per-token
    losses ``(T, rows, seq)``."""
    p = exit_distribution(g)
    h_p = entropy(p)
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    total = jnp.mean(jnp.sum(p * nll, axis=0) - beta * h_p)
    return {"total": total, "loss": total, "token_nll": nll[-1], "p": p,
            **{f"ut_nll_{t + 1}": jnp.mean(nll[t]) for t in range(len(nll))},
            "ut_exit_entropy": jnp.mean(h_p),
            "ut_expected_steps": jnp.mean(
                jnp.sum(steps[:, None, None] * p, axis=0)),
            "ut_steps": jnp.float32(p.shape[0])}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: the exit distribution's expected next-token loss
    less ``beta`` times its entropy."""
    return loss_parts(params, tokens, conf)["total"]
