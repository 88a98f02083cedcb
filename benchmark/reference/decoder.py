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

What the train loop asks of a reference module (``"reference"`` in a
configuration file names one): ``loss_parts(params, tokens, conf)`` — a
dict with ``total``, the training loss the check compares, its parts
under the names the program's ``loss_fn`` reports them, and
``token_nll (rows, seq)``, each position's next-token loss;
``loss_rtol(n)``, the tolerance of the mean for a sample of ``n`` tokens;
``STEP_METRICS``, name -> (how the window keeps it: ``sum`` or ``max``;
the value ``correct`` requires of that, or None), the step metrics
fetched with every loss; and, for ``rehearse_compile.py``, the jitted
``layer`` with ``layer_kwargs(conf)``, its static arguments.

The check compares two numbers (``loops/train.py::reference_check``), on
parameters whose norm weights are drawn from the seed (at step 0 they are
all 1 and what they norm has unit RMS, so a missing norm alone would not
show).  The program computes in bfloat16 activations with float32 softmax
statistics, logits and loss; this file in float32 throughout.

1. The PER-TOKEN losses, program against this file, as the root of their
   mean squared difference in nats.  This is the number that sees
   PRECISION: it averages thousands of squares, so it is steady from seed
   to seed (within 16 % over a dozen seeds in every cell), where the mean
   loss below is a signed sum that can cancel to nothing on any seed.
   Its limit is the CONFIGURATION's (``"check": {"token_nll_rms": ...}``
   in its file), because the program's own bfloat16 rounding adds up with
   depth (0.0078 at 4 layers, 0.0193 at 20).  It is set between two chip
   readings at the cell's size, which ``benchmark/control.py`` takes and
   PERF.md section 6 (PR 29) lists for every cell: the sound program's
   largest, and the smallest of the control — this reference in the
   program's place with its matrices rounded to the precision below the
   bfloat16 the configurations state (8-bit floats; int8 read beside).
2. The MEAN training loss, relative (``LOSS_RTOL``, 1e-4 at 4096 tokens
   and more; the v5e read 2.5e-7 to 2.3e-5 over 92 checks, PR 29).  It
   guards the STRUCTURE of ``loss_fn`` — the mean, the auxiliary terms a
   model adds — and what changes the function itself: a missing norm,
   mask, RoPE or residual moves it by about the sampling spread of a
   4096-token mean, 1.5e-3.  It canNOT see precision: bfloat16 logits
   and softmax under a float32 mean read 4.7e-6 to 1.5e-4, among the
   sound readings (PR 29's chip run; an earlier text of this file said
   "about 1e-3", which that run refuted).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

LOSS_RTOL = 1e-4
Q_BLOCK = 1024
STEP_METRICS: Dict[str, Any] = {}  # a dense step reports nothing to hold


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    4096 and more of a chip check; the rounding noise of a mean grows as
    one over the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (4096 / tokens) ** 0.5)


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
def _head_nll(x, final_norm, lm_head, targets, *, eps):
    """Final norm, head and each position's next-token loss ``(rows,
    seq)``, all float32."""
    x = rms_norm(x, final_norm.astype(jnp.float32), eps)
    logp = jax.nn.log_softmax(x @ lm_head.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, jax.Array]:
    """Of ``tokens`` (rows, seq + 1) under the configuration file ``conf``:
    ``token_nll`` and its mean, which is a dense decoder's training loss."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            x = layer(x, params["layers"], i, **layer_kwargs(conf))
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=float(conf["rms_norm_eps"]))
    nll = jnp.mean(token_nll)
    return {"total": nll, "loss": nll, "token_nll": token_nll}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (rows, seq + 1)."""
    return loss_parts(params, tokens, conf)["total"]
