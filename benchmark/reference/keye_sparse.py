"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(``model_type`` ``KeyeVL2``; ``config.json`` of
huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B; the vision tower is left
out), as ONE CHIP'S SHARE of a layer divided over several where the
configuration file states one: a Qwen3-MoE decoder whose attention reads,
for each query, the ``sa_config.topk`` keys a learned INDEXER picks — the
lightning indexer and top-k selection of DeepSeek's sparse attention
(arXiv:2512.02556 §2.1) on a grouped-query model.

With ``n(.)`` an RMSNorm with its own learned weight and ``rms_norm_eps``, a
layer is

    a = x + Attn(n_in(x))
    y = a + Experts(n_post(a))                    every layer an expert layer

- ``Attn(h)``: ``q = h W_q`` (heads x d_head), ``k, v = h W_k, h W_v``
  (kv_heads x d_head; query head ``j`` reads KV head ``j // group``); an
  RMSNorm over EACH head's ``d_head`` on q and k (one weight of a head's
  size each); q and k rotated over the whole head at ``rope_theta``
  (dimension ``i`` paired with ``i + d_head / 2``); scale ``d_head ** -0.5``.
- The indexer reads the SAME normed input, DETACHED (``hd =
  stop_gradient(h)``): ``qI = hd W_qI`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``kI = LayerNorm(hd W_kI)`` (ONE key head; weight,
  bias, ``rms_norm_eps``), both rotated by the tables of the same theta over
  their own ``indexer_head_dim`` channels, and with ``H`` the index heads
  and ``di`` their size

      I[t, s] = sum_j (hd_t W_w)_j H ** -0.5 di ** -0.5 relu(qI_t,j . kI_s)

  for ``s <= t`` (arXiv:2512.02556 eq. 1).
- The selection ``S_t``: the ``topk`` keys ``s <= t`` of highest ``I[t,
  s]`` — every ``s <= t`` while ``t < topk`` —, TIES TO THE LOWER ``s``
  (``jax.lax.top_k`` on the row, which breaks ties so).
- ``o_t,head = softmax over s in S_t of (q_t,head . k_s * scale)`` applied
  to ``v_s``; nothing outside ``S_t`` is read.  Then ``W_o``.
- The indexer's loss (§2.1.1, the sparse training stage): ``p_t`` = the
  heads' attention probabilities over ``S_t``, summed over the heads and
  divided by their count, DETACHED; a layer's ``L_idx = mean_t KL(p_t ||
  softmax over S_t of I[t, .])``; the step's loss is ``L_lm +
  idx_loss_coef x`` (the layers' mean of ``L_idx``).  The indexer's
  parameters have a gradient from ``L_idx`` alone, the rest from ``L_lm``
  alone.
- ``Experts(h)``: ``mellum.py``'s — softmax over ALL the published experts,
  the ``num_experts_per_tok`` largest, gates over the sum of the chosen
  (``norm_topk_prob``), no shared expert; OF A SHARE the sum runs over the
  experts HELD (``num_local_experts`` of them from ``first_expert`` on).
- One last RMSNorm; an untied head (of a share, over the vocabulary's slice).

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel, no cache, no batching: the index scores are one
``(seq, seq)`` matrix a layer, written ``Q_BLOCK`` queries at a time (a
``lax.map`` over the blocks) so that it fits (at 16384 tokens the 16 heads'
products of a block are 0.5 GB, the 32 heads' scores 1.07 GB), the selection
a boolean mask scattered from ``top_k``'s keys, the softmax masked, the KL as
written; the experts are a
LOOP over the held ones (``xing4.py``'s ``held_experts``).  It reads the
PROGRAM'S parameters as they lie (``attn_norm``, ``wq``, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm``, ``wq_idx``, ``wk_idx``, ``w_idx``,
``k_idx_norm``, ``k_idx_bias``, ``mlp_norm``, ``router`` and the three
``(L, E', ...)``) and upcasts one layer, and inside it one expert, at a time.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  TWO selections are
discontinuous here: the experts' (``olmoe.py``) and the keys'.  Where a
query's ``topk``-th and next index scores lie closer than the program's
bfloat16 stream moves them, program and reference read different keys
there; the two candidates are then keys the indexer ranks alike, one of
``topk`` terms of a softmax (the configuration file's ``check.why`` has what
the chip read).
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
# step may lose an assignment to a held expert, and none may select a pair
# more or fewer than ``topk`` keys a query give (the program counts the mask
# it made); the rest is kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "dsa_selected_off": ("sum", 0.0),
                "dsa_selected_share": ("max", None),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None)}


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def selected(index, first, topk: int):
    """The boolean mask ``(rows, block, seq)`` of a block of queries from
    their index scores ``index (rows, block, seq)``: ``-inf`` after the
    query, the ``topk`` highest of what is left, ties to the lower key."""
    rows, block, seq = index.shape
    causal = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
    _, keys = jax.lax.top_k(jnp.where(causal, index, -jnp.inf),
                            min(topk, seq))
    picked = jnp.zeros((rows, block, seq), bool).at[
        jnp.arange(rows)[:, None, None], jnp.arange(block)[None, :, None],
        keys].set(True)
    return picked & causal


def sparse_attention(u, p, *, heads, kv_heads, index_heads, topk, theta,
                     eps):
    """``Attn`` on the normed ``u (rows, seq, d)``: ``(what W_o gives, the
    layer's L_idx, the pairs selected)``."""
    rows, seq, _ = u.shape
    d_head = p["wq"].shape[-1] // heads
    di = p["wk_idx"].shape[-1]
    cos, sin = rope_tables(seq, d_head, theta)
    q = apply_rope(rms_norm((u @ p["wq"]).reshape(
        rows, seq, heads, d_head), p["q_norm"], eps), cos, sin)
    k = apply_rope(rms_norm((u @ p["wk"]).reshape(
        rows, seq, kv_heads, d_head), p["k_norm"], eps), cos, sin)
    v = (u @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)

    ud = jax.lax.stop_gradient(u)
    cos_i, sin_i = rope_tables(seq, di, theta)
    q_idx = apply_rope((ud @ p["wq_idx"]).reshape(
        rows, seq, index_heads, di), cos_i, sin_i)
    k_idx = apply_rope(layer_norm(
        ud @ p["wk_idx"], p["k_idx_norm"], p["k_idx_bias"], eps
    )[:, :, None, :], cos_i, sin_i)[:, :, 0, :]
    w = (ud @ p["w_idx"]) * (index_heads ** -0.5 * di ** -0.5)

    def block(args):
        """``Q_BLOCK`` queries from ``first`` on: what they read, their KL
        summed, the pairs they select."""
        first, q_, q_idx_, w_ = args
        # + 0.0: a score of -0.0 (every ReLU shut under negative weights)
        # IS 0.0, a tie like any other, and top_k would order the two
        index = jnp.einsum(
            "bqh,bqhs->bqs", w_, jax.nn.relu(jnp.einsum(
                "bqhd,bsd->bqhs", q_idx_, k_idx))) + 0.0
        live = selected(index, first, topk)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_, k) * d_head ** -0.5
        prob = jax.nn.softmax(
            jnp.where(live[:, None, None], scores, -jnp.inf), axis=-1)
        target = jax.lax.stop_gradient(jnp.sum(prob, axis=(1, 2)) / heads)
        logq = jax.nn.log_softmax(jnp.where(live, index, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(
            live, jax.scipy.special.xlogy(target, target)
            - target * jnp.where(live, logq, 0.0), 0.0))
        return (jnp.einsum("bkgqs,bskd->bqkgd", prob, v), kl,
                jnp.sum(live))

    # one block at a time (``lax.map``: written as a Python loop the
    # compiler keeps several blocks' 1 GB of scores alive at once)
    size = min(Q_BLOCK, seq)
    blocks = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape(rows, seq // size, size, *x.shape[2:]), 1, 0)
    out, kl, pairs = jax.lax.map(block, (
        jnp.arange(0, seq, size), blocks(q), blocks(q_idx), blocks(w)))
    o = jnp.moveaxis(out, 0, 1).reshape(rows, seq, heads * d_head)
    return o @ p["wo"], jnp.sum(kl) / (rows * seq), jnp.sum(pairs)


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("heads", "kv_heads", "index_heads", "topk", "theta", "eps", "k",
           "renormalise", "first")


def _one_layer(x, stack, place, kw):
    """One layer on float32 ``x (rows, seq, d)``: ``(x, the experts chosen
    (T, k), the load-balancing loss, L_idx, the pairs selected)``."""
    p = {name: a[place] if name in _BIG else a[place].astype(jnp.float32)
         for name, a in stack.items()}
    eps = kw["eps"]
    a, kl, pairs = sparse_attention(
        rms_norm(x, p["attn_norm"], eps), p, **{
            name: kw[name] for name in (
                "heads", "kv_heads", "index_heads", "topk", "theta", "eps")})
    x = x + a
    y, experts, balance = expert_ffn(
        rms_norm(x, p["mlp_norm"], eps), p, k=kw["k"],
        renormalise=kw["renormalise"], first=kw["first"])
    return x + y, experts, balance, kl, pairs


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``; ``kw`` is ``layer_kwargs``'."""
    return _one_layer(x, layers, index, kw)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _jitted_layer(x, stack, place, **kw):
    """``place`` is traced: one program, not one a layer."""
    return _one_layer(x, stack, place, kw)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    group = conf["sa_config"]
    if group.get("indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError("an indexer of several key heads")
    return dict(heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                index_heads=group["indexer_num_heads"], topk=group["topk"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                k=conf["num_experts_per_tok"],
                renormalise=bool(conf["norm_topk_prob"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` (the mean next-token loss, ``lm_loss`` too), ``idx_loss`` (the
    layers' mean of ``L_idx``), ``aux_loss`` (the load-balancing loss, the
    layers' mean), ``total`` (the three at the file's ``idx_loss_coef`` and
    ``router_aux_loss_coef``), ``token_nll (rows, seq)``, ``experts`` (a
    layer's choices ``(T, k)``, in order), ``moe_held_share`` and
    ``dsa_selected_share`` (the pairs selected over the causal ones, the
    layers' mean)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    stack = params["layers"]
    depth = conf["num_hidden_layers"]
    rows, seq = inputs.shape
    chosen, balance, kl, share = [], 0.0, 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for place in range(depth):
            x, experts, b, kl_, pairs = _jitted_layer(x, stack, place, **kw)
            chosen.append(experts)
            balance, kl = balance + b / depth, kl + kl_ / depth
            share = share + pairs / (rows * (seq * (seq + 1) // 2)) / depth
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
    first, held = kw["first"], stack["w_gate"].shape[1]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e in chosen) / depth
    nll = jnp.mean(token_nll)
    return {"loss": nll, "lm_loss": nll, "idx_loss": kl, "aux_loss": balance,
            "total": (nll + conf.get("idx_loss_coef", 1.0) * kl
                      + conf.get("router_aux_loss_coef", 0.0) * balance),
            "token_nll": token_nll, "experts": chosen,
            "moe_held_share": held_share, "dsa_selected_share": share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: cross-entropy, the indexers' loss, the
    load-balancing term."""
    return loss_parts(params, tokens, conf)["total"]
