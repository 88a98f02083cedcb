"""Plain reference of the decoder the benchmark's configurations name.

A pre-norm, decoder-only transformer written from the published
descriptions (Mistral 7B, arXiv:2310.06825; DeepSeek LLM,
arXiv:2401.02954; both the architecture of Touvron et al. 2023): token
embedding; per layer RMSNorm -> q/k/v projections -> rotary position
embedding on q and k -> causal softmax attention in which each KV head is
shared by a group of query heads -> output projection -> residual;
RMSNorm -> SwiGLU feed-forward -> residual; final RMSNorm; untied output
head; mean next-token cross-entropy.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bfloat16 passes).  No kernel, no cache, no
``shard_map``, and nothing imported from ``ray_tpu``: the yardstick may
not move when the program does.

It reads the PROGRAM'S parameters as they lie (a dict ``embed (V, d)``,
``layers`` of arrays stacked on a leading layer dimension — ``attn_norm``,
``wq (L, d, h*dh)``, ``wk``, ``wv``, ``wo (L, h*dh, d)``, ``mlp_norm``,
``w_gate (L, d, m)``, ``w_up``, ``w_down (L, m, d)`` — ``final_norm`` and
``lm_head (d, V)``), in whatever dtype and sharding they have, and
upcasts ONE layer at a time: float32 copies of a whole model do not fit
beside the train state.  Each layer is one jitted call whose computation
follows its inputs' sharding, so on a mesh no chip holds the whole model.

Departures from the published text, each stated:

- RoPE pairs dimension ``i`` with ``i + d_head/2`` (the "rotate-half"
  form of the ``transformers`` implementation whose ``config.json`` the
  configurations cite).  Mistral's own reference code pairs ``2i`` with
  ``2i+1``; the two differ by a fixed permutation of each head's
  dimensions in ``wq``/``wk``, which random weights cannot tell apart.
- Query head ``j`` reads KV head ``j // (heads / kv_heads)``, as
  ``repeat_kv`` in ``transformers`` lays them out.
- Mistral's 4096-token sliding window is not applied: the benchmark's
  sequences are at most 4096 long, where it never cuts.
- Attention is computed for ``Q_BLOCK`` query positions at a time
  against the whole prefix, only to bound the score matrix's memory.

Tolerance (``LOSS_RTOL``): the program computes in bfloat16 activations
with float32 softmax statistics, logits and loss; this file in float32
throughout.  Per-token losses then differ by about 1e-2 with either
sign, and their mean over some 4096 tokens by about 1e-4 of a loss near
ln(vocab) ~ 10.5-11.5: about 1e-5 relative.  The v5e read 1.6e-7 to
2.8e-5 (my chip runs, PR 22: three cells, 43 runs, 20 seeds); the
tolerance is 1e-4, a small factor above.  It also covers the one known
difference in the mathematics: the program's RMSNorm epsilon is fixed at
1e-6 (``ray_tpu/ops/layers.py``) where Mistral publishes 1e-5, which
moves unit-variance activations by 4.5e-6 relative.  What should fail
it: bfloat16 logits or a bfloat16 softmax (about 1e-3); a missing causal
mask, RoPE or residual (another function of the same weights, whose loss
differs by about the sampling spread of a 4096-token mean, 1.5e-3).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

LOSS_RTOL = 1e-4
Q_BLOCK = 1024


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope_tables(seq: int, d_head: int, theta: float):
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: (rows, seq, heads, d_head); pairs (i, i + d_head/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v):
    """q: (rows, seq, kv_heads, group, d_head); k, v: (rows, seq,
    kv_heads, d_head).  Softmax over the keys at or before each query."""
    seq, d_head = q.shape[1], q.shape[-1]
    key_pos = jnp.arange(seq)
    out = []
    for start in range(0, seq, Q_BLOCK):
        qb = q[:, start:start + Q_BLOCK]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / jnp.sqrt(
            jnp.float32(d_head))
        query_pos = start + jnp.arange(qb.shape[1])
        visible = key_pos[None, :] <= query_pos[:, None]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def layer(x, layers, index, *, heads, kv_heads, theta, eps):
    """One decoder layer on float32 ``x`` (rows, seq, d), with layer
    ``index`` of the stacked parameters upcast to float32."""
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
    x = x + o @ p["wo"]
    h = rms_norm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_loss(x, final_norm, lm_head, targets, *, eps):
    x = rms_norm(x, final_norm.astype(jnp.float32), eps)
    logp = jax.nn.log_softmax(x @ lm_head.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (rows, seq + 1) under
    the configuration file ``conf`` (public ``config.json`` key names)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            x = layer(x, params["layers"], i,
                      heads=conf["num_attention_heads"],
                      kv_heads=conf["num_key_value_heads"],
                      theta=float(conf["rope_theta"]),
                      eps=float(conf["rms_norm_eps"]))
        return _head_loss(x, params["final_norm"], params["lm_head"],
                          targets, eps=float(conf["rms_norm_eps"]))
