"""Plain reference of the Xing4.0 decoder (``model_type`` ``xing4_0``;
``config.json`` of huggingface.co/XingChen-AGI/Xing4.0-29B-A4B), as ONE
CHIP'S SHARE of a layer divided over several where the configuration file
states one.  Its layers, each from its source:

- **The residual**: manifold-constrained hyper-connections
  (arXiv:2512.24880 §4, over hyper-connections, arXiv:2409.19606 §3).  With
  n = ``hc_mult`` a token holds ``X (n, d)``.  Round EACH block ``F`` (the
  mixer, then the FFN): ``x~ = RMSNorm(vec X)`` over all n d numbers, no
  learned weight; ``H~pre = a_pre (x~ P_pre) + b_pre``, ``H~post = a_post
  (x~ P_post) + b_post`` (n each), ``H~res = a_res mat(x~ P_res) + b_res``
  (n x n, row-major); ``Hpre = sigmoid(H~pre)``, ``Hpost = 2
  sigmoid(H~post)``, ``Hres = SK(clip(H~res, mhc_h_res_clamp_min,
  mhc_h_res_clamp_max))`` where SK takes ``exp`` and then
  ``hc_sinkhorn_iters`` times divides every row by its sum + ``hc_eps``
  and every column by its; ``X' = Hres X + Hpost^T F(Hpre X)``, ``F`` with
  its own pre-norm over d.  ASSUMED (the configuration file says so too):
  the embedding is copied to the n streams at the input and the streams
  are SUMMED before the last norm (arXiv:2409.19606 §3); the clip sits
  before ``exp`` and ``hc_eps`` in both divisions; the norm's epsilon is
  ``rms_norm_eps``.  The program keeps P_pre, P_post, P_res as the columns
  of one matrix ``(n d, 2 n + n^2)`` and the biases and scalars likewise.
- **The mixer**: latent attention as DeepSeek-V3's (arXiv:2412.19437
  §2.1.1).  ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb``
  -> heads x [nope | rope]; ``[c_kv | k_r] = h W_kva``; ``[k_nope | v] =
  RMSNorm(c_kv) W_kvb`` -> heads x [nope | v]; ``k = [k_nope | RoPE(k_r)]``
  with the ONE rotary head shared by all, q's rotary part through the same
  RoPE; causal softmax of ``q k^T s`` times v, ``s = (nope + rope)^-0.5 x
  (0.1 mscale_all_dim ln(factor) + 1)^2``; output heads x v -> ``W_o``.
  RoPE's frequencies are YaRN's (arXiv:2309.00071 §3.2: a dimension that
  turns more than ``beta_fast`` times in the original context keeps its
  frequency, fewer than ``beta_slow`` has it divided by ``factor``, a
  linear ramp between; its cos/sin factor is ``mscale / mscale_all_dim`` =
  1 here).  ASSUMED: RoPE pairs dimension i with i + rope/2 (the
  published code interleaves 2i, 2i + 1: a fixed permutation of ``W_qb``'s
  and ``W_kva``'s columns, which random weights cannot tell apart).
- **The FFN**: layers before ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``.  Later ones (arXiv:2412.19437 §2.1.2): ``s =
  sigmoid(h W_r)`` over ALL ``n_routed_experts`` (published count); the
  ``num_experts_per_tok`` largest of ``s + b`` (``noaux_tc``; ``n_group``
  1: no group limit; ``b`` reaches the selection only); ``g =
  routed_scaling_factor x s_i / sum of the chosen s`` (``norm_topk_prob``);
  ``y = sum g_i E_i(h) + E_shared(h)``, every E a SwiGLU of
  ``moe_intermediate_size`` (the shared one ``n_shared_experts`` times
  that).  OF A SHARE the sum runs over the experts HELD (the leading
  dimension of the program's expert tensors, from ``first_expert`` on):
  what an absent expert would add is left out, here as in the program,
  and that partial result goes on to the next layer.
- **The predicted-ahead module** (arXiv:2412.19437 §2.2; 1 of them): ``h'_t
  = W_p [RMSNorm(h_t) ; RMSNorm(Emb(x_(t+1)))]``, ``h_t`` the model's
  summed streams before the last norm; one more expert layer of its own;
  its own last norm (the published checkpoints of that paper keep one;
  ASSUMED); the model's embedding and head; target ``x_(t+2)``.  The loss
  is ``main + mtp_loss_coef x mtp`` with ``mtp`` the mean over the
  positions that have a target (all but the last); 0.3 ASSUMED (that
  paper's weight for most of its training).

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; nothing is imported from
``ray_tpu``.  The streams are a real ``(rows, seq, n, d)`` array and the
maps real ``(rows, seq, n, n)`` matrices (the program keeps the streams
side by side and the maps transposed); the experts are a LOOP over the
held ones, each applied to every token at the weight ``sum_j g_j [e_j ==
e]`` — no sort, no gather, no kernel; attention is computed for
``Q_BLOCK`` queries at a time against the whole prefix and the head for
``HEAD_BLOCK`` positions, only to bound memory.  It reads the PROGRAM'S
parameters as they lie (``ray_tpu/models/llama.py``: ``layers`` a tuple
of two stacks, the dense run and the expert run; ``mtp``) and upcasts one
layer, and inside it one expert, at a time.

Not modelled, with the published value that makes it nothing:
``attention_bias`` false; ``moe_layer_freq`` 1 (every later layer has
experts); ``n_group`` = ``topk_group`` = 1; ``ep_size`` 1 is the published
file's serving default and says nothing of training.  The selection
bias's UPDATE is the train step's and not a part of the loss
(``tests/test_latent_streams.py`` holds it to the rule).

The contract (``decoder.py``'s docstring): ``loss_parts``, ``loss_rtol``,
``STEP_METRICS``, ``layer`` + ``layer_kwargs``.  ``layer(x, layers, index,
...)`` is layer ``index`` (static) of the model on the streams ``(rows,
seq, n, d)``; handed ``(rows, seq, d)`` (``rehearse_compile.py``) it
copies that to the streams first, as the model's input is.  The
selection of experts is discontinuous as OLMoE's is (``olmoe.py`` says
what that does to the per-token comparison) and a chosen expert enters at
a gate near 0.5, so both comparisons are noisier here than in any other
cell: the tolerance of the mean is this file's (``LOSS_RTOL``), the limit
of the per-token comparison the configuration file's, each from chip
readings.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import apply_rope, rms_norm

# The tolerance of the MEAN loss (main + weighted predicted-ahead), at
# 8192 tokens and more.  It guards the STRUCTURE of ``loss_fn`` and cannot
# see precision (``decoder.py``).  Here the per-token losses stand 0.042 -
# 0.052 nats apart from this file's (swapped experts at gates near 0.5: the
# configuration file's ``check``), so a mean of 8192 of them has a noise of
# 0.045 / sqrt(8192) = 5e-4 nats, 3.7e-5 of a loss of 13.3: the v5e read
# 6.2e-6 to 6.2e-5 over 12 seeds (``control.py``, PR 34), where the dense
# decoder's 1e-4 would be 2.3 standard deviations and refuse one run in
# fifty.  3e-4 is five times the largest sound reading; what changes the
# function moves it by 1e-3 and more (``tests/test_latent_streams.py``: every
# changed part; at the cell's size a dropped predicted-ahead term is 0.23,
# a dropped last-position mask 1e-3).
LOSS_RTOL = 3e-4
Q_BLOCK = 1024
HEAD_BLOCK = 2048
# What the window fetches with every loss (``decoder.py`` has the form): no
# step may lose an assignment to an expert that is held; the busiest
# expert's load, the share of the rows that is here and the predicted-ahead
# loss are kept.
STEP_METRICS = {"moe_dropped": ("sum", 0.0),
                "moe_load_max_over_mean": ("max", None),
                "moe_held_share": ("max", None),
                "mtp_loss": ("max", None)}


def loss_rtol(tokens: int) -> float:
    """The tolerance for a sample of ``tokens`` tokens: ``LOSS_RTOL`` at the
    8192 and more of a chip check; the noise of a mean grows as one over
    the root of the sample, so a smaller one gets that much more."""
    return LOSS_RTOL * max(1.0, (8192 / tokens) ** 0.5)


def yarn_tables(seq: int, dim: int, theta: float, scaling: Dict):
    """cos and sin ``(seq, dim / 2)`` at YaRN's frequencies."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def dimension_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dimension_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(scaling["beta_slow"])), dim - 1)
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def softmax_scale(conf: Dict) -> float:
    scaling = conf["rope_scaling"]
    temperature = 0.1 * scaling["mscale_all_dim"] * math.log(
        scaling["factor"]) + 1.0
    return (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5 \
        * temperature ** 2


def sinkhorn(h, iters: int, eps: float):
    """``exp(h (..., n, n))``, then ``iters`` times rows over their sums +
    ``eps`` and columns over theirs."""
    m = jnp.exp(h)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def maps(xs, proj, bias, scale, *, eps, iters, hc_eps, clamp):
    """``Hpre, Hpost (rows, seq, n)`` and ``Hres (rows, seq, n, n)`` of the
    streams ``xs (rows, seq, n, d)``."""
    rows, seq, n, d = xs.shape
    flat = xs.reshape(rows, seq, n * d)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    raw = flat @ proj
    pre = jax.nn.sigmoid(scale[0] * raw[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(scale[1] * raw[..., n:2 * n] + bias[n:2 * n])
    res = (scale[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(
        rows, seq, n, n)
    return pre, post, sinkhorn(jnp.clip(res, *clamp), iters, hc_eps)


def around(xs, p, block: str, fn, hc):
    """``X' = Hres X + Hpost^T fn(Hpre X)`` for one block of a layer."""
    pre, post, res = maps(xs, p[f"hc_{block}_proj"], p[f"hc_{block}_bias"],
                          p[f"hc_{block}_scale"], **hc)
    y = fn(jnp.einsum("bsn,bsnd->bsd", pre, xs))
    return (jnp.einsum("bsij,bsjd->bsid", res, xs)
            + post[..., :, None] * y[..., None, :])


def causal_attention(q, k, v, scale):
    """q, k ``(rows, seq, heads, d_qk)``, v ``(rows, seq, heads, d_v)``:
    softmax of ``q k^T * scale`` over the keys at or before each query."""
    seq = q.shape[1]
    key_pos = jnp.arange(seq)
    out = []
    for start in range(0, seq, Q_BLOCK):
        qb = q[:, start:start + Q_BLOCK]
        scores = jnp.einsum("bqhd,bshd->bhqs", qb, k) * scale
        query_pos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(key_pos[None, :] <= query_pos[:, None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("bhqs,bshd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def latent_attention(x, p, *, heads, nope, rope, v_dim, latent, theta, eps,
                     scale, scaling):
    rows, seq, _ = x.shape
    cos, sin = yarn_tables(seq, rope, theta, dict(scaling))
    h = rms_norm(x, p["attn_norm"], eps)
    q = (rms_norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"]).reshape(
        rows, seq, heads, nope + rope)
    down = h @ p["wkv_a"]
    kv = (rms_norm(down[..., :latent], p["kv_a_norm"], eps)
          @ p["wkv_b"]).reshape(rows, seq, heads, nope + v_dim)
    k_rope = apply_rope(down[..., None, latent:], cos, sin)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (rows, seq, heads, rope))],
        axis=-1)
    o = causal_attention(q, k, kv[..., nope:], scale)
    return o.reshape(rows, seq, heads * v_dim) @ p["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(n, router, bias, k: int, factor: float):
    """``n (T, d)`` -> gates and experts ``(T, k)``: sigmoid scores, the
    ``k`` largest of score + bias, gates the chosen scores over their sum,
    times ``factor``."""
    scores = jax.nn.sigmoid(n @ router)
    _, experts = jax.lax.top_k(scores + bias, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return factor * chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts


def held_experts(n, gates, experts, first: int, w_gate, w_up, w_down):
    """``sum_e weight_e * expert_e(n)`` over the experts HELD (``first``
    on, as many as ``w_gate`` has), one at a time; ``weight_e (T,)`` is
    the token's gate for ``e``, or 0."""
    def one(y, ws):
        e, wg, wu, wd = ws
        wg, wu, wd = (w.astype(jnp.float32) for w in (wg, wu, wd))
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(n, wg, wu, wd), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (first + jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def expert_ffn(x, p, *, k, factor, first, eps):
    """Routed experts held here plus the shared expert, of ``x (rows, seq,
    d)``; also the experts chosen ``(T, k)``."""
    rows, seq, d = x.shape
    n = rms_norm(x, p["mlp_norm"], eps).reshape(rows * seq, d)
    gates, experts = route(n, p["router"], p["router_bias"], k, factor)
    y = held_experts(n, gates, experts, first, p["w_gate"], p["w_up"],
                     p["w_down"])
    y = y + swiglu(n, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(rows, seq, d), experts


_STATIC = ("dense", "heads", "nope", "rope", "v_dim", "latent", "theta",
           "eps", "scale", "scaling", "k", "factor", "first", "iters",
           "hc_eps", "clamp", "n")
_BIG = ("w_gate", "w_up", "w_down")  # an expert stack: upcast one at a time


def _one_layer(xs, stack, place, is_dense: bool, kw):
    p = {name: a[place] if name in _BIG and not is_dense
         else a[place].astype(jnp.float32) for name, a in stack.items()}
    hc = dict(eps=kw["eps"], iters=kw["iters"], hc_eps=kw["hc_eps"],
              clamp=kw["clamp"])
    attention = functools.partial(
        latent_attention, p=p, **{name: kw[name] for name in (
            "heads", "nope", "rope", "v_dim", "latent", "theta", "eps",
            "scale", "scaling")})
    xs = around(xs, p, "attn", attention, hc)
    chosen = []
    if is_dense:
        def ffn(x):
            return swiglu(rms_norm(x, p["mlp_norm"], kw["eps"]),
                          p["w_gate"], p["w_up"], p["w_down"])
    else:
        def ffn(x):
            y, experts = expert_ffn(x, p, k=kw["k"], factor=kw["factor"],
                                    first=kw["first"], eps=kw["eps"])
            chosen.append(experts)
            return y
    return around(xs, p, "ffn", ffn, hc), (chosen[0] if chosen else None)


def _streams(x, n: int):
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


@functools.partial(jax.jit, static_argnums=(2,), static_argnames=_STATIC)
def layer(x, layers, index, **kw):
    """Layer ``index`` (static) of the model on the float32 streams ``x
    (rows, seq, n, d)`` (or ``(rows, seq, d)``, copied to them first);
    ``layers`` the program's two stacks, the ``dense`` leading layers and
    the expert layers; ``kw`` is ``layer_kwargs``'.  Returns the streams."""
    if x.ndim == 3:
        x = _streams(x, kw["n"])
    is_dense = index < kw["dense"]
    stack = layers[0] if is_dense else layers[1]
    return _one_layer(x, stack, index if is_dense else index - kw["dense"],
                      is_dense, kw)[0]


def layer_kwargs(conf: Dict) -> Dict[str, Any]:
    """``layer``'s static arguments under the configuration file ``conf``
    (public ``config.json`` key names)."""
    return dict(
        dense=conf["first_k_dense_replace"], n=conf["hc_mult"],
        heads=conf["num_attention_heads"], nope=conf["qk_nope_head_dim"],
        rope=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
        latent=conf["kv_lora_rank"], theta=float(conf["rope_theta"]),
        eps=float(conf["rms_norm_eps"]), scale=softmax_scale(conf),
        scaling=tuple(sorted(conf["rope_scaling"].items())),
        k=conf["num_experts_per_tok"],
        factor=float(conf["routed_scaling_factor"]),
        first=int(conf.get("first_expert", 0)),
        iters=conf["hc_sinkhorn_iters"], hc_eps=float(conf["hc_eps"]),
        clamp=(float(conf["mhc_h_res_clamp_min"]),
               float(conf["mhc_h_res_clamp_max"])))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_nll(x, final_norm, lm_head, targets, *, eps):
    """Last norm, head and each position's loss for ``targets``, ``(rows,
    seq)``, ``HEAD_BLOCK`` positions at a time."""
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


@functools.partial(jax.jit, static_argnames=("eps",))
def _ahead_input(h, embedded, mtp, *, eps):
    return jnp.concatenate(
        [rms_norm(h, mtp["h_norm"].astype(jnp.float32), eps),
         rms_norm(embedded, mtp["e_norm"].astype(jnp.float32), eps)],
        axis=-1) @ mtp["proj"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,), static_argnames=_STATIC)
def _jitted_layer(xs, stack, place, is_dense, **kw):
    """``place`` is traced: one program a stack, not one a layer."""
    return _one_layer(xs, stack, place, is_dense, kw)


def _run_layers(xs, stack, is_dense: bool, kw):
    """Every layer of one stack over the streams; the experts chosen, a
    layer."""
    chosen = []
    for place in range(jax.tree.leaves(stack)[0].shape[0]):
        xs, experts = _jitted_layer(xs, stack, place, is_dense, **kw)
        chosen.append(experts)
    return xs, chosen


def loss_parts(params: Dict[str, Any], tokens: jax.Array, conf: Dict
               ) -> Dict[str, Any]:
    """Of ``tokens (rows, seq + 1)`` under the configuration file ``conf``:
    ``loss`` (the mean next-token loss), ``mtp_loss`` (the predicted-ahead
    module's, over the positions that have a target), ``total`` (``loss +
    mtp_loss_coef x mtp_loss``), ``token_nll (rows, seq)``, ``experts``
    (a layer that has them, the module's last: ``(T, k)``) and
    ``moe_held_share`` (the choices that name a held expert over all of
    them, the mean over those layers)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = layer_kwargs(conf)
    eps, n = kw["eps"], kw["n"]
    dense_stack, expert_stack = params["layers"]
    mtp = params["mtp"]
    with jax.default_matmul_precision("highest"):
        xs = _streams(jnp.take(params["embed"], inputs, axis=0).astype(
            jnp.float32), n)
        xs, _ = _run_layers(xs, dense_stack, True, kw)
        xs, chosen = _run_layers(xs, expert_stack, False, kw)
        h = jnp.sum(xs, axis=-2)
        token_nll = _head_nll(h, params["final_norm"], params["lm_head"],
                              targets, eps=eps)
        # the module: position t meets token t + 1 and predicts token t + 2
        embedded = jnp.take(params["embed"], targets, axis=0).astype(
            jnp.float32)
        small = {name: a for name, a in mtp.items() if name != "layers"}
        ys = _streams(_ahead_input(h, embedded, small, eps=eps), n)
        ys, more = _run_layers(ys, mtp["layers"], False, kw)
        ahead_nll = _head_nll(
            jnp.sum(ys, axis=-2), mtp["final_norm"], params["lm_head"],
            jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1),
            eps=eps)[:, :-1]
    chosen += more
    held = expert_stack["w_gate"].shape[1]
    held_share = sum(
        jnp.mean(((e >= kw["first"]) & (e < kw["first"] + held)).astype(
            jnp.float32)) for e in chosen) / len(chosen)
    nll, mtp_nll = jnp.mean(token_nll), jnp.mean(ahead_nll)
    return {"loss": nll, "mtp_loss": mtp_nll,
            "total": nll + conf["mtp_loss_coef"] * mtp_nll,
            "token_nll": token_nll, "experts": chosen,
            "moe_held_share": held_share}


def loss(params: Dict[str, Any], tokens: jax.Array, conf: Dict) -> jax.Array:
    """The training loss: next-token cross-entropy plus the weighted
    predicted-ahead loss."""
    return loss_parts(params, tokens, conf)["total"]
