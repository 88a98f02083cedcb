"""Plain reference of SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``;
``config.json`` of huggingface.co/JetLM/SDAR-30B-A3B-Chat; arXiv:2510.06303)
TRAINED BY BLOCK DIFFUSION (arXiv:2503.09573; the masked-diffusion loss of
arXiv:2406.07524 / arXiv:2502.09992), as ONE CHIP'S SHARE of a layer divided
over several where the configuration file states one.

The network is Qwen3-MoE's.  With ``n(.)`` an RMSNorm with its own learned
weight and ``rms_norm_eps``, a layer is

    a = x + Attn(n_in(x))
    y = a + Experts(n_post(a))                    every layer an expert layer

- ``Attn(h)``: ``q = h W_q`` (heads x d_head), ``k, v = h W_k, h W_v``
  (kv_heads x d_head; query head ``j`` reads KV head ``j // group``); an
  RMSNorm over EACH head's ``d_head`` on q and k; q and k rotated over the
  whole head at ``rope_theta`` (dimension ``i`` paired with ``i + d_head /
  2``); scale ``d_head ** -0.5``; the softmax under THE BLOCK RULE below.
- ``Experts(h)``: ``mellum.py``'s — softmax over ALL the published experts,
  the ``num_experts_per_tok`` largest, gates over the sum of the chosen
  (``norm_topk_prob``), no shared expert; OF A SHARE the sum runs over the
  experts HELD (``num_experts`` of them from ``first_expert`` on).
- One last RMSNorm; an untied head (of a share, over the vocabulary's slice).

THE OBJECTIVE.  ``block_diffusion`` in the configuration file is the group
``{"block_length": B, "mask_token_id", "eps", "noise_seed"}``.  Of a
sequence ``x0`` of ``L`` tokens, position ``i`` lies in block ``b(i) = i //
B``.

- Noise (``corrupt``, written out below, the same draws as the program's):
  the key of a step is ``fold_in(PRNGKey(noise_seed), step)`` split in two;
  one ``t ~ U(0, 1)`` a sequence, ``p = (1 - eps) t + eps``; one uniform a
  position, ``m_i = [u_i < p]``; ``xt_i = mask_token_id if m_i else x0_i``.
  WHICH positions are masked is ``m``, never ``xt == mask_token_id``.
- ONE pass over ``[xt ; x0]``, 2 L rows, both halves at positions 0..L-1.
  Query row ``r``, key column ``c``, each in the noised half N or the clean
  half C (``seen``, the dense ``(2 L, 2 L)`` mask, from these four cases):

      r in C, c in C: seen iff b(c) <= b(r)    block-causal, the own block whole
      r in N, c in C: seen iff b(c) <  b(r)    the clean past
      r in N, c in N: seen iff b(c) == b(r)    the own noised block, both ways
      r in C, c in N: never

- ``logits = head(n_last(h_N))`` over the noised half's L rows, and

      loss = (1 / (rows L)) sum_i m_i (1 / p) (-log softmax(logits_i)[x0_i])

  (position ``i``'s target is ITS OWN token: no shift); ``total = loss +
  router_aux_loss_coef x`` (the layers' mean load-balancing loss).

What the train loop's per-token row means here.  The loop scores row ``i``
of the program's ``forward`` on the id ``tokens[i + 1]``; under this
objective ``forward`` IS the denoising pass at step 0 and returns the noised
stream's logits, so that row PROBES the log-softmax of the timed path's own
logits at a seeded random id (any id probes a random model's log-softmax
alike).  ``token_nll`` below is defined as exactly that:
``-log softmax(logits_i)[tokens[i + 1]]``.  It is no loss of this model;
``loss_parts["total"]`` is.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel: the scores are written ``Q_BLOCK`` query rows at a
time against all 2 L keys under ``lax.map`` (at L = 8192 the 32 heads'
scores of a block are 1.07 GB; a Python loop of blocks keeps many alive),
the mask computed from the two indices, the experts a LOOP over the held
ones.  It reads the PROGRAM'S parameters as they lie (``attn_norm``,
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``, ``mlp_norm``,
``router`` and the three ``(L, E', ...)``) and upcasts one layer, and inside
it one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import apply_rope, rms_norm, rope_tables
from benchmark.reference.mellum import expert_ffn, loss_rtol  # noqa: F401
from benchmark.reference.xing4 import _head_nll

Q_BLOCK = 512
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to a held expert, and in none may the kernels'
# schedule differ from the four cases on a single pair of the strip the
# program tests; the rest is kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "bd_mask_off": ("sum", 0.0),
                "attn_bd_executed_share": ("max", None),
                "bd_masked_share": ("max", None),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None)}


def corrupt(tokens, group: Dict, step=0):
    """``(xt, m, p)`` of ``tokens (rows, L)``: the noised copy, which
    positions were masked, the sequences' ``p (rows, 1)``."""
    group = dict(group)
    key_t, key_m = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(group["noise_seed"]), step))
    t = jax.random.uniform(key_t, (tokens.shape[0], 1), jnp.float32)
    p = (1.0 - group["eps"]) * t + group["eps"]
    m = jax.random.uniform(key_m, tokens.shape, jnp.float32) < p
    return jnp.where(m, group["mask_token_id"], tokens).astype(
        tokens.dtype), m, p


def seen(rows, length: int, block: int):
    """The four cases for the query rows ``rows`` (indices into ``[N ; C]``)
    against all ``2 length`` key columns: booleans ``(len(rows), 2
    length)``."""
    columns = jnp.arange(2 * length)
    r_clean, c_clean = rows[:, None] >= length, columns[None, :] >= length
    r_b = (rows[:, None] % length) // block
    c_b = (columns[None, :] % length) // block
    return jnp.where(
        r_clean, c_clean & (c_b <= r_b),            # C on C; C on N: never
        jnp.where(c_clean, c_b < r_b, c_b == r_b))  # N on C; N on N


def block_attention(u, p, *, heads, kv_heads, block, theta, eps):
    """``Attn`` on the normed ``u (rows, 2 L, d)``, the two streams of one
    sequence side by side."""
    rows, both, _ = u.shape
    length = both // 2
    d_head = p["wq"].shape[-1] // heads
    # a noised token and its clean copy share a position
    cos, sin = (jnp.concatenate([t, t]) for t in rope_tables(
        length, d_head, theta))
    q = apply_rope(rms_norm((u @ p["wq"]).reshape(
        rows, both, heads, d_head), p["q_norm"], eps), cos, sin)
    k = apply_rope(rms_norm((u @ p["wk"]).reshape(
        rows, both, kv_heads, d_head), p["k_norm"], eps), cos, sin)
    v = (u @ p["wv"]).reshape(rows, both, kv_heads, d_head)
    q = q.reshape(rows, both, kv_heads, heads // kv_heads, d_head)

    def some(args):
        first, q_ = args
        live = seen(first + jnp.arange(q_.shape[1]), length, block)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_, k) * d_head ** -0.5
        prob = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", prob, v)

    size = min(Q_BLOCK, both)
    out = jax.lax.map(some, (jnp.arange(0, both, size), jnp.moveaxis(
        q.reshape(rows, both // size, size, *q.shape[2:]), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(rows, both, heads * d_head) \
        @ p["wo"]


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("heads", "kv_heads", "block", "theta", "eps", "k", "renormalise",
           "first")


def _one_layer(x, stack, place, kw):
    """One layer on float32 ``x (rows, 2 L, d)``: ``(x, the experts chosen
    (T, k), the load-balancing loss)``."""
    p = {name: a[place] if name in _BIG else a[place].astype(jnp.float32)
         for name, a in stack.items()}
    eps = kw["eps"]
    x = x + block_attention(
        rms_norm(x, p["attn_norm"], eps), p, **{
            name: kw[name] for name in (
                "heads", "kv_heads", "block", "theta", "eps")})
    y, experts, balance = expert_ffn(
        rms_norm(x, p["mlp_norm"], eps), p, k=kw["k"],
        renormalise=kw["renormalise"], first=kw["first"])
    return x + y, experts, balance


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model as a step runs it, for the
    compile rehearsal: ``x (rows, L, d)`` stands for EACH of the two streams
    (a step's layer sees 2 L rows a sequence); ``kw`` is ``layer_kwargs``'."""
    return _one_layer(jnp.concatenate([x, x], axis=1), layers, index, kw)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _jitted_layer(x, stack, place, **kw):
    """``place`` is traced: one program, not one a layer."""
    return _one_layer(x, stack, place, kw)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                block=dict(conf["block_diffusion"])["block_length"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                k=conf["num_experts_per_tok"],
                renormalise=bool(conf["norm_topk_prob"]),
                first=int(conf.get("first_expert", 0)))


def noised_stream(params: Dict[str, Any], x0: jax.Array, conf: Dict, step=0):
    """The pass over ``[xt ; x0]`` of ``x0 (rows, L)``: ``(the noised half's
    stream before the last norm (rows, L, d), m, p, each layer's chosen
    experts, the layers' mean load-balancing loss)``."""
    kw = layer_kwargs(conf)
    stack, depth = params["layers"], conf["num_hidden_layers"]
    xt, m, p = corrupt(x0, conf["block_diffusion"], step)
    x = jnp.take(params["embed"], jnp.concatenate([xt, x0], axis=1),
                 axis=0).astype(jnp.float32)
    chosen, balance = [], 0.0
    for place in range(depth):
        x, experts, b = _jitted_layer(x, stack, place, **kw)
        chosen.append(experts)
        balance = balance + b / depth
    return x[:, :x0.shape[1]], m, p, chosen, balance


def logits(params: Dict[str, Any], x0: jax.Array, conf: Dict) -> jax.Array:
    """The noised stream's logits ``(rows, L, vocab)`` at step 0: what the
    program's ``forward`` returns (the tests' use; the check never holds
    them)."""
    with jax.default_matmul_precision("highest"):
        h = noised_stream(params, x0, conf)[0]
        return rms_norm(h, params["final_norm"].astype(jnp.float32), float(
            conf["rms_norm_eps"])) @ params["lm_head"].astype(jnp.float32)


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, L + 1)`` under the configuration file ``conf``, at
    step 0: ``loss`` (the denoising loss of ``x0 = tokens[:, :-1]``),
    ``aux_loss``, ``total``, ``token_nll (rows, L)`` (the PROBE the module's
    docstring defines: row ``i``'s ``-log softmax`` at ``tokens[i + 1]``),
    ``bd_masked_share``, ``experts`` and ``moe_held_share``."""
    x0, probe = tokens[:, :-1], tokens[:, 1:]
    eps = float(conf["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h, m, p, chosen, balance = noised_stream(params, x0, conf)
        head = functools.partial(_head_nll, h, params["final_norm"],
                                 params["lm_head"], eps=eps)
        own, token_nll = head(x0), head(probe)
    loss = jnp.sum(own * m / p) / own.size
    first, held = int(conf.get("first_expert", 0)), \
        params["layers"]["w_gate"].shape[1]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e in chosen) / len(chosen)
    return {"loss": loss, "aux_loss": balance,
            "total": loss + conf.get("router_aux_loss_coef", 0.0) * balance,
            "token_nll": token_nll, "experts": chosen,
            "bd_masked_share": jnp.mean(m.astype(jnp.float32)),
            "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss at step 0: the denoising cross-entropy and the
    load-balancing term."""
    return loss_parts(params, tokens, conf)["total"]
