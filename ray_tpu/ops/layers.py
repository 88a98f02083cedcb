"""Elementwise/normalization layer primitives (XLA-fused by design).

These stay as plain jnp: XLA fuses them into neighboring matmuls, so a
Pallas version would only add compile surface.  (Pallas is reserved for ops
XLA can't schedule well: attention inner loops, ring collect-compute
overlap — see ops/attention.py, ops/ring_attention.py.)
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm (Llama-style, no mean subtraction).  Stats in f32."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    """LayerNorm over the last dimension, statistics in float32."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


def yarn_inv_freq(head_dim: int, theta: float, *, factor: float,
                  original: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """YaRN's frequencies (arXiv:2309.00071 §3.2, "NTK-by-parts"):
    a dimension that turns more than ``beta_fast`` times within the
    ``original`` context keeps its frequency, one that turns fewer than
    ``beta_slow`` times has it divided by ``factor``, and a linear ramp
    over the dimensions lies between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    kept = 1.0 / theta ** exponent

    def dimension_of(turns):  # the dimension that turns this often
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_of(beta_fast)), 0)
    high = min(math.ceil(dimension_of(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return kept / factor * ramp + kept * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature, ``0.1 mscale ln(factor) + 1``: the
    softmax scale of a model that states ``mscale_all_dim`` is multiplied
    by its square."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(seq_len: int, head_dim: int, theta: float = 10000.0,
         offset=0, inv_freq: Optional[jax.Array] = None
         ) -> Tuple[jax.Array, jax.Array]:
    """Rotary position embedding tables (cos, sin): (seq_len, head_dim/2).
    ``offset`` may be traced (e.g. an 'sp' rank offset inside shard_map);
    ``inv_freq`` replaces the plain frequencies (``yarn_inv_freq``)."""
    freqs = inv_freq if inv_freq is not None else 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32) + offset
    angles = jnp.outer(t, freqs)
    return jnp.cos(angles), jnp.sin(angles)


def rope_type(scaling) -> str:
    """The type a public file's rope-scaling group (a dict, or its items)
    names, under the key either generation of the files gives it;
    ``default``, the plain tables, for no group."""
    scaling = dict(scaling or ())
    return scaling.get("rope_type", scaling.get("type")) or "default"


def scaled_rope(seq_len: int, head_dim: int, theta: float, scaling=(),
                offset=0) -> Tuple[jax.Array, jax.Array]:
    """``rope``'s tables under a public file's scaling group ``scaling`` (a
    dict or its items; none, or one of type ``default``: the plain tables):
    of a ``yarn`` group ``yarn_inv_freq``'s frequencies, and cos and sin
    TIMES the group's ``attention_factor`` — so q and k both carry it and
    the scores its square.  Where the group states none it is ``0.1 ln
    factor + 1``, or, of a group that states ``mscale`` and
    ``mscale_all_dim``, the ratio of the two temperatures (1 where they
    are equal: the model then scales its softmax itself)."""
    scaling = dict(scaling or ())
    kind = rope_type(scaling)
    if kind == "default":
        return rope(seq_len, head_dim, theta, offset=offset)
    if kind != "yarn":
        raise NotImplementedError(f"rope scaling of type {kind!r}")
    factor = scaling["factor"]
    cos, sin = rope(seq_len, head_dim, theta, offset=offset,
                    inv_freq=yarn_inv_freq(
                        head_dim, theta, factor=factor,
                        original=scaling["original_max_position_embeddings"],
                        beta_fast=scaling.get("beta_fast", 32.0),
                        beta_slow=scaling.get("beta_slow", 1.0)))
    on_tables = scaling.get("attention_factor")
    if on_tables is None:
        m, m_all = scaling.get("mscale"), scaling.get("mscale_all_dim")
        on_tables = (yarn_mscale(factor, m) / yarn_mscale(factor, m_all)
                     if m and m_all else yarn_mscale(factor, 1.0))
    if on_tables == 1.0:  # no op added to the program
        return cos, sin
    return cos * on_tables, sin * on_tables


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, s, h, d); cos/sin: (s, d/2).  Rotate-half formulation.  Where
    the heads lie side by side, ``(b, s, h x d)``, and a head fills whole
    lane blocks the same sums are a kernel's (``ops/rotary.py``): in plain
    XLA they cost tables as wide as x and slices off the lane tiles."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(logits)`` made nearly doubly stochastic by ``iters`` rounds of
    Sinkhorn-Knopp (arXiv:2512.24880 §4.2): each round divides every row
    by its sum + ``eps``, then every column by its.  ``logits (n, n, ...)``:
    axis 0 the rows, axis 1 the columns, the rest a batch kept MINOR (a
    (T, n, n) layout would fill a sixteenth of each vector register)."""
    def one_round(m, _):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=0, keepdims=True) + eps), None

    # a loop, not ``iters`` copies of the round in the program: a third of
    # the step's compile time at 20 rounds (PERF.md §6, PR 34)
    return jax.lax.scan(one_round, jnp.exp(logits), None, length=iters)[0]


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """SwiGLU activation: silu(gate) * up."""
    return jax.nn.silu(gate) * up


def repeat_kv_heads(q: jax.Array, k: jax.Array, v: jax.Array):
    """Broadcast GQA kv heads up to q's head count (validated)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h == h_kv:
        return k, v
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    rep = h // h_kv
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
