"""Plain reference of the Laguna decoder (``model_type`` ``laguna``;
``config.json`` of huggingface.co/poolside/Laguna-XS.2), as ONE CHIP'S SHARE
of a layer divided over several where the configuration file states one.

With ``n(.)`` an RMSNorm with its own learned weight and ``rms_norm_eps``, a
layer ``l`` is

    a = x + Attn_l(n_attn(x))
    y = a + FFN_l(n_mlp(a))                             TWO norms a layer

- ``Attn_l(h)``: the layer's KIND is ``layer_types[l]`` and its count of
  QUERY heads ``H = num_attention_heads_per_layer[l]`` (48 in a
  ``full_attention`` layer, 64 in a ``sliding_attention`` one; the 8 KV
  heads and the head size of 128 are the model's).  ``q = h W_q`` (H x
  d_head), ``k, v = h W_k, h W_v`` (kv_heads x d_head); query head ``j``
  reads KV head ``j // (H / kv_heads)``.  q and k are rotated by the KIND's
  group of ``rope_parameters``: over the FIRST ``r = partial_rotary_factor x
  d_head`` dimensions of a head, dimension ``i < r / 2`` paired with ``i + r
  / 2``, the frequencies reckoned over ``r`` (plain ``theta^(-2 i / r)``, or
  YaRN's by parts over ``r``), cos and sin times the group's
  ``attention_factor``; dimensions ``r .. d_head - 1`` pass through and
  carry NO factor.  Query ``i`` sees key ``j <= i`` and, in a sliding layer,
  ``i - j < sliding_window``.  ``o = softmax(q k^T / sqrt(d_head)) v``; the
  gate is ONE number a head, ``g = sigmoid(h W_g)`` with ``W_g (d, H)``, and
  ``Attn = (o_j g_j)_j W_o``.
- ``FFN_l``: ``mlp_layer_types[l]`` ``dense`` is a SwiGLU of
  ``intermediate_size``; ``sparse`` is ``s = sigmoid(h W_r)`` over ALL the
  published experts, the ``num_experts_per_tok`` experts with the largest
  ``s + b`` (``b``: the selection bias, which reaches the selection only),
  gates ``w_e = s_e / sum of the chosen s`` times
  ``moe_routed_scaling_factor`` ON THE OUTPUT, and ``FFN = SwiGLU_shared(h) +
  sum_e w_e SwiGLU_e(h)`` (``afmoe.py``'s ``route`` and ``expert_ffn``: its
  1e-20 in the division moves no float32 sum of eight sigmoids).  OF A SHARE
  the sum runs over the experts HELD (``xing4.py``'s ``held_experts``).
- embedded tokens as they are; one last RMSNorm; an untied head.

The configuration file lists under ``assumed`` what the catalog row does not
settle (the gate's form, the router's score function and selection bias, no
QK-norm, YaRN over the rotary width).

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  No kernel: the mask is written out ``Q_BLOCK`` queries at a time
against every key under ``lax.map`` (written as a Python loop the chip's
compiler keeps every block's scores alive: ``keye_sparse.py``; a window saves
this file nothing); the experts are a loop over the held ones; the head runs
``HEAD_BLOCK`` positions at a time (``xing4.py``'s ``_head_nll``).  It reads
the PROGRAM'S parameters as they lie (``ray_tpu/models/llama.py``: ``layers``
a tuple of stacks, one a maximal run of layers of one kind (mixer, FFN) —
``attn_norm``, ``wq`` and ``wo`` and ``wg`` AT THE KIND'S HEAD COUNT, ``wk``,
``wv``; ``mlp_norm`` and ``w_gate``, ``w_up``, ``w_down`` of a dense FFN, or
``router (L, d, E)``, ``router_bias (L, E)``, the three ``(L, E', ...)`` and
``shared_gate``, ``shared_up``, ``shared_down`` of an expert layer) and
upcasts one layer, and inside it one expert, at a time.  The head count is
read off the CONFIGURATION (``num_attention_heads_per_layer``), never off a
tensor's shape: a program that gave a sliding layer 48 heads hands this file
tensors it refuses to reshape.

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import expert_ffn
from benchmark.reference.decoder import apply_rope, rms_norm
from benchmark.reference.granite_hybrid import locate
from benchmark.reference.mellum import rope_tables
from benchmark.reference.xing4 import _head_nll, swiglu

# The tolerance of the MEAN loss at 8192 tokens and more: the share cells'
# (``afmoe.py``, ``mellum.py``).  The configuration file's ``check.why`` has
# the chip readings it stands over.
LOSS_RTOL = 3e-4
Q_BLOCK = 256     # 64 heads x 256 x 16384 float32 scores: 1.07 GB a block
# What the window fetches with every loss (``decoder.py`` has the form).
# HELD: no step may lose an assignment to an expert that is here; a full
# layer ran 48 query heads and rotated 64 of a head's 128 dimensions, a
# sliding layer ran 64 heads under a window of 512 keys (the PUBLISHED
# model's: a tiny model's counts are its own, and only a chip run holds a
# window to these; a window a key short moves no loss a check resolves —
# Mellum2's 1023 read among its sound readings — so the counter holds it).
# Kept: the
# busiest expert's load, the share of the rows that is here, the rows the
# kernels visit, and what the windowed kernels compute over what the window
# leaves.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "attn_q_heads_full": ("max", 48.0),
                "attn_q_heads_window": ("max", 64.0),
                "attn_rotary_width_full": ("max", 64.0),
                "attn_window_keys": ("max", 512.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "moe_rows_visited_share": ("max", None),
                "attn_window_executed_share": ("max", None),
                "attn_window_masked_tile_share": ("max", None)}
SLIDING = "sliding_attention"


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


def rotary_width(d_head: int, group: Dict) -> int:
    """The dimensions of a head a layer of this group rotates."""
    return int(d_head * group.get("partial_rotary_factor", 1.0))


def rotate(x, group: Dict):
    """``x (rows, seq, heads, d_head)`` with the first ``r`` dimensions of
    every head rotated by the group's tables over ``r`` (``mellum.py``'s
    ``rope_tables``: plain or YaRN's frequencies, times the
    ``attention_factor``) and the rest as they came."""
    r = rotary_width(x.shape[-1], group)
    cos, sin = rope_tables(x.shape[1], r, group)
    return jnp.concatenate([apply_rope(x[..., :r], cos, sin), x[..., r:]],
                           axis=-1)


def masked_attention(q, k, v, window: Optional[int]):
    """q: (rows, seq, kv_heads, group, d_head); k, v: (rows, seq, kv_heads,
    d_head).  Softmax over the keys ``j`` that query ``i`` sees: ``j <= i``
    and, under a ``window``, ``i - j < window``; ``Q_BLOCK`` queries at a
    time."""
    rows, seq, d_head = q.shape[0], q.shape[1], q.shape[-1]
    key_pos = jnp.arange(seq)[None, :]

    def some(args):
        first, q_ = args
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_, k) / math.sqrt(d_head)
        query_pos = (first + jnp.arange(q_.shape[1]))[:, None]
        seen = key_pos <= query_pos
        if window is not None:
            seen = seen & (query_pos - key_pos < window)
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", prob, v)

    size = min(Q_BLOCK, seq)
    while seq % size:   # a sample no multiple of the block: a divisor of it
        size -= 1
    out = jax.lax.map(some, (jnp.arange(0, seq, size), jnp.moveaxis(
        q.reshape(rows, seq // size, size, *q.shape[2:]), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def attention(u, p, *, heads: int, kv_heads: int, group: Dict,
              window: Optional[int]):
    """``Attn`` on the normed ``u (rows, seq, d)`` of a layer of ``heads``
    query heads whose rotary group is ``group``; ``window``: the keys a
    query sees, or None (all before it)."""
    rows, seq, _ = u.shape
    d_head = p["wk"].shape[-1] // kv_heads
    q = rotate((u @ p["wq"]).reshape(rows, seq, heads, d_head), group)
    k = rotate((u @ p["wk"]).reshape(rows, seq, kv_heads, d_head), group)
    v = (u @ p["wv"]).reshape(rows, seq, kv_heads, d_head)
    q = q.reshape(rows, seq, kv_heads, heads // kv_heads, d_head)
    o = masked_attention(q, k, v, window).reshape(rows, seq, heads, d_head)
    gate = jax.nn.sigmoid(u @ p["wg"])                  # (rows, seq, heads)
    return (o * gate[..., None]).reshape(rows, seq, heads * d_head) @ p["wo"]


_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time
_STATIC = ("kinds", "heads", "kv_heads", "groups", "eps", "window", "k",
           "scale", "first")


def _one_layer(x, stack, place, kind, kw):
    """One layer of ``kind`` on float32 ``x (rows, seq, d)``; returns ``(x,
    the experts chosen (T, k) or None)``."""
    mixer, ffn = kind
    p = {name: a[place] if name in _BIG and ffn == "moe"
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    eps = kw["eps"]
    x = x + attention(
        rms_norm(x, p["attn_norm"], eps), p, heads=dict(kw["heads"])[mixer],
        kv_heads=kw["kv_heads"], group=dict(dict(kw["groups"])[mixer]),
        window=kw["window"] if mixer == SLIDING else None)
    h = rms_norm(x, p["mlp_norm"], eps)
    if ffn == "dense":
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    y, experts = expert_ffn(h, p, k=kw["k"], scale=kw["scale"],
                            first=kw["first"])
    return x + y, experts


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on float32 ``x (rows, seq,
    d)``, whatever its kind; ``kw`` is ``layer_kwargs``'."""
    kind, stack, place = locate(kw["kinds"], layers)[index]
    return _one_layer(x, stack, place, kind, kw)[0]


@functools.partial(jax.jit, static_argnums=(3,), static_argnames=_STATIC)
def _jitted_layer(x, stack, place, kind, **kw):
    """``place`` is traced: one program a stack, not one a layer."""
    return _one_layer(x, stack, place, kind, kw)


def kinds(conf: Dict) -> Tuple[Tuple[str, str], ...]:
    """(mixer, FFN) of the layers that are run, in order."""
    depth = conf["num_hidden_layers"]
    return tuple(
        (mixer, "dense" if ffn == "dense" else "moe")
        for mixer, ffn in zip(conf["layer_types"][:depth],
                              conf["mlp_layer_types"][:depth]))


def heads_by_kind(conf: Dict) -> Tuple[Tuple[str, int], ...]:
    """``((kind, its layers' query heads), ...)`` of the layers that are
    run; a file that gives one kind two counts is no Laguna model."""
    depth = conf["num_hidden_layers"]
    pairs = sorted(set(zip(conf["layer_types"][:depth],
                           conf["num_attention_heads_per_layer"][:depth])))
    if len({kind for kind, _ in pairs}) != len(pairs):
        raise ValueError(f"one head count a kind of layer: {pairs}")
    return tuple(pairs)


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names); the rotary groups as sorted item
    tuples: hashable."""
    groups = tuple(sorted(
        (kind, tuple(sorted(group.items())))
        for kind, group in conf["rope_parameters"].items()
        if isinstance(group, dict)))
    return dict(kinds=kinds(conf), heads=heads_by_kind(conf),
                kv_heads=conf["num_key_value_heads"], groups=groups,
                eps=float(conf["rms_norm_eps"]),
                window=int(conf["sliding_window"]),
                k=conf["num_experts_per_tok"],
                scale=float(conf["moe_routed_scaling_factor"]),
                first=int(conf.get("first_expert", 0)))


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` = ``total`` (the mean next-token loss: the model adds no
    auxiliary term), ``token_nll (rows, seq)``, ``experts`` (an expert
    layer's choices ``(T, k)``, in order) and ``moe_held_share`` (the
    choices that name a held expert over all of them, the mean over those
    layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], inputs, axis=0).astype(jnp.float32)
        for kind, stack, place in locate(kw["kinds"], params["layers"]):
            x, experts = _jitted_layer(x, stack, place, kind, **kw)
            if experts is not None:
                chosen.append((experts, stack["w_gate"].shape[1]))
        token_nll = _head_nll(x, params["final_norm"], params["lm_head"],
                              targets, eps=kw["eps"])
    first = kw["first"]
    held_share = sum(
        jnp.mean(((e >= first) & (e < first + held)).astype(jnp.float32))
        for e, held in chosen) / max(len(chosen), 1)
    nll = jnp.mean(token_nll)
    return {"loss": nll, "total": nll, "token_nll": token_nll,
            "experts": [e for e, _ in chosen], "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: mean next-token cross-entropy."""
    return loss_parts(params, tokens, conf)["total"]
