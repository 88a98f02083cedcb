"""Flagship model family: Llama-style decoder LM, TPU-first.  A layer is a
MIXER (softmax attention | a Mamba-2 state-space mixer) followed by an FFN
(dense SwiGLU | dropless experts), and a model is a pattern of such layers.

Pure-functional design: params are a pytree of arrays, every tensor
dimension has a *logical axis name*, and one rules table
(``parallel.sharding.DEFAULT_RULES``) maps names to mesh axes — so the same
model runs DP, FSDP, 2D (fsdp x tp), MoE-EP, or sequence-parallel by
swapping rules, never editing model code.

TPU-first choices:
- layers are *stacked* on a leading "layer" dim and driven by ``lax.scan``
  (+``jax.checkpoint``): one trace/compile of a single layer regardless of
  depth, rematerialized backward to trade FLOPs for HBM.
- bf16 activations/params with f32 RMSNorm stats and f32 logits/loss — the
  MXU-native recipe.
- attention is pluggable: pallas flash (ops/attention.py), ring over 'sp'
  (ops/ring_attention.py), Ulysses all-to-all, or the XLA reference — all
  numerically interchangeable (tested).
- MoE layers are dropless (ops/moe.py): every token reaches all of its
  ``num_selected`` experts through a grouped matmul over the sorted
  assignments; expert tensors are sharded over 'ep', each rank computes its
  own experts' rows inside a shard_map and the partial outputs are summed.
- a model whose layers differ (``layer_types``: granite-4.0-h's Mamba-2
  layers with an attention layer every tenth) is scanned by maximal RUNS of
  one kind, each run one ``lax.scan`` over its own stacked parameters
  (``params["layers"]`` is then a tuple of stacks, one a run); a model of
  one kind is one run and ``params["layers"]`` the one stack.

Reference counterpart: none in Ray core (no tensor ops); RLlib's model zoo
(``rllib/models/catalog.py``) plays the "models shipped with the framework"
role, and its JAX support is a 299-LoC stub (``rllib/models/jax/``) — cited
for parity, not design.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops import attention, moe
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.ops.layers import (
    rms_norm, rope, apply_rope, swiglu, repeat_kv_heads,
)
from ray_tpu.ops.moe import moe_block
from ray_tpu.ops.ssm import causal_conv1d, gated_rms_norm, ssd_chunked
from ray_tpu.parallel.mesh import (
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_SP, AXIS_TP,
)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalAxisRules, with_logical_constraint,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    embed_dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    mlp_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "flash"          # flash | ring | ulysses | reference
    num_experts: int = 0              # 0 = dense FFN
    num_selected: int = 2             # experts a token goes to (all of them)
    norm_topk_prob: bool = False      # renormalise the selected gates
    aux_loss_coef: float = 0.01       # load-balancing loss
    z_loss_coef: float = 0.0          # router z-loss
    norm_eps: float = 1e-6            # every RMSNorm
    qk_norm: bool = False             # RMSNorm over the q and k projections
    remat: bool = True
    # The mixer of each layer, "attention" | "mamba"; only the first
    # ``num_layers`` entries are the model, empty = attention everywhere.
    layer_types: Tuple[str, ...] = ()
    ssm_heads: int = 0                # Mamba-2: heads x head_dim = inner width
    ssm_head_dim: int = 64
    ssm_state: int = 128              # state size a head (d_state)
    ssm_groups: int = 1               # groups that share B and C
    ssm_conv: int = 4                 # width of the causal depthwise conv
    ssm_chunk: int = 256              # tokens a chunk of the scan
    position_embedding: str = "rope"  # rope | nope (no position signal)
    attention_multiplier: Optional[float] = None  # None: head_dim ** -0.5
    embedding_multiplier: float = 1.0  # on the embedded tokens
    residual_multiplier: float = 1.0  # on what each block adds to the stream
    logits_scaling: float = 1.0       # logits are divided by it
    tie_embeddings: bool = False      # the head reads the embedding table

    def __post_init__(self):
        # a configuration file hands a list: keep the config hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(_MIXERS)
        if unknown:
            raise ValueError(
                f"layer_types {sorted(unknown)}: not in {sorted(_MIXERS)}")
        if self.layer_types and len(self.layer_types) < self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_layers is {self.num_layers}")

    @property
    def qkv_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def layer_runs(self) -> Tuple[Tuple[str, int], ...]:
        """The model as maximal runs of one mixer: ((kind, layers), ...)."""
        kinds = self.layer_types[:self.num_layers] or (
            ("attention",) * self.num_layers)
        runs = []
        for kind in kinds:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return tuple((kind, n) for kind, n in runs)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(embed_dim=5120, num_layers=40, num_heads=40,
                           num_kv_heads=40, mlp_dim=13824, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """CI-sized config: runs on one CPU device in seconds."""
        defaults = dict(vocab_size=256, embed_dim=64, num_layers=2,
                        num_heads=4, num_kv_heads=4, head_dim=16, mlp_dim=128,
                        max_seq_len=64, dtype=jnp.float32, remat=False,
                        attn_impl="reference")
        defaults.update(kw)
        return LlamaConfig(**defaults)


def _attention_shapes(cfg: LlamaConfig):
    d, h, kvd = cfg.embed_dim, cfg.qkv_dim, cfg.kv_dim
    shapes = {
        "attn_norm": ((d,), ("layer", "embed")),
        "wq": ((d, h), ("layer", "kernel_in", "heads")),
        "wk": ((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wv": ((d, kvd), ("layer", "kernel_in", "kv_heads")),
        "wo": ((h, d), ("layer", "heads", "kernel_in")),
    }
    if cfg.qk_norm:  # over the whole projection, before heads and RoPE
        shapes.update({"q_norm": ((h,), ("layer", "heads")),
                       "k_norm": ((kvd,), ("layer", "kv_heads"))})
    return shapes


def _mamba_shapes(cfg: LlamaConfig):
    """A Mamba-2 mixer: ``ssm_in`` gives [z | x B C | dt] side by side
    (the published layout of ``in_proj``); the convolution runs over
    x, B and C; ``dt_bias``, ``A_log`` and ``D`` are a number a head."""
    d, inner, conv = cfg.embed_dim, cfg.ssm_inner, cfg.ssm_conv_dim
    return {
        "ssm_norm": ((d,), ("layer", "embed")),
        "ssm_in": ((d, inner + conv + cfg.ssm_heads),
                   ("layer", "kernel_in", "ssm_inner")),
        "conv_w": ((cfg.ssm_conv, conv), ("layer", None, "ssm_inner")),
        "conv_b": ((conv,), ("layer", "ssm_inner")),
        "dt_bias": ((cfg.ssm_heads,), ("layer", None)),
        "A_log": ((cfg.ssm_heads,), ("layer", None)),
        "D": ((cfg.ssm_heads,), ("layer", None)),
        "gate_norm": ((inner,), ("layer", "ssm_inner")),
        "ssm_out": ((inner, d), ("layer", "ssm_inner", "kernel_in")),
    }


def _ffn_shapes(cfg: LlamaConfig):
    d, m = cfg.embed_dim, cfg.mlp_dim
    if cfg.num_experts:
        e = cfg.num_experts
        return {
            "mlp_norm": ((d,), ("layer", "embed")),
            "router": ((d, e), ("layer", "kernel_in", None)),
            "w_gate": ((e, d, m), ("layer", "expert", "kernel_in", "mlp")),
            "w_up": ((e, d, m), ("layer", "expert", "kernel_in", "mlp")),
            "w_down": ((e, m, d), ("layer", "expert", "mlp", "kernel_in")),
        }
    return {
        "mlp_norm": ((d,), ("layer", "embed")),
        "w_gate": ((d, m), ("layer", "kernel_in", "mlp")),
        "w_up": ((d, m), ("layer", "kernel_in", "mlp")),
        "w_down": ((m, d), ("layer", "mlp", "kernel_in")),
    }


_MIXER_SHAPES = {"attention": _attention_shapes, "mamba": _mamba_shapes}


def _layer_shapes(cfg: LlamaConfig, mixer: str = "attention"
                  ) -> Dict[str, Tuple[Tuple[int, ...], Tuple]]:
    """name -> (shape-per-layer, logical axes incl. the stacked 'layer'
    dim) of a layer with this mixer: the mixer's tensors, then the FFN's."""
    return {**_MIXER_SHAPES[mixer](cfg), **_ffn_shapes(cfg)}


def _per_run(runs: list):
    """``params["layers"]`` (or a tree shaped like it) from one entry a
    run: the entry itself for a model of one kind of layer."""
    return runs[0] if len(runs) == 1 else tuple(runs)


def _runs(cfg: LlamaConfig, layers) -> list:
    """[(mixer, that run's entry of ``layers``), ...]: ``_per_run``'s
    inverse."""
    runs = cfg.layer_runs
    if len(runs) == 1:
        layers = (layers,)
    return [(kind, lp) for (kind, _), lp in zip(runs, layers)]


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    axes = {
        "embed": ("vocab", "kernel_in"),
        "layers": _per_run([
            {k: ax for k, (_, ax) in _layer_shapes(cfg, kind).items()}
            for kind, _ in cfg.layer_runs]),
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("kernel_in", "vocab")
    return axes


def _ssm_init(name: str, key: jax.Array, shape, cfg: LlamaConfig):
    """The Mamba-2 reference code's initialisation of what is not a
    projection: A uniform in 1..16 (kept as its log), dt log-uniform in
    1e-3..1e-1 through the inverse of the softplus it passes, D = 1, the
    convolution as torch's ``Conv1d`` (uniform within 1/sqrt(width))."""
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":
        dt = jnp.maximum(jnp.exp(jnp.log(1e-3) + u * jnp.log(1e2)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return (2.0 * u - 1.0) * cfg.ssm_conv ** -0.5      # conv_w, conv_b


_SSM_INIT = ("conv_w", "conv_b", "dt_bias", "A_log", "D")


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Scaled-normal init (fan-in), params in ``cfg.param_dtype``.  A tied
    table is initialised as the head it also is (fan-in: the step-0 loss
    is then ln(vocab) to a hundredth)."""
    run_shapes = [(n, _layer_shapes(cfg, kind)) for kind, n in cfg.layer_runs]
    n_tensors = sum(len(shapes) for _, shapes in run_shapes) + 3
    keys = iter(jax.random.split(key, n_tensors))

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.param_dtype)

    runs = []
    for n, shapes in run_shapes:
        layers = {}
        for name, (shape, _) in shapes.items():
            full = (n,) + shape
            if name.endswith("norm"):
                layers[name] = jnp.ones(full, cfg.param_dtype)
            elif name in _SSM_INIT:
                layers[name] = _ssm_init(name, next(keys), full, cfg).astype(
                    cfg.param_dtype)
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                layers[name] = norm_init(next(keys), full, fan_in)
        runs.append(layers)
    params = {
        "embed": norm_init(next(keys), (cfg.vocab_size, cfg.embed_dim),
                           cfg.embed_dim if cfg.tie_embeddings else 1.0),
        "layers": _per_run(runs),
        "final_norm": jnp.ones((cfg.embed_dim,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(
            next(keys), (cfg.embed_dim, cfg.vocab_size), cfg.embed_dim)
    return params


def _sm_scale(cfg: LlamaConfig) -> float:
    if cfg.attention_multiplier is None:
        return cfg.head_dim ** -0.5
    return cfg.attention_multiplier


def _attention(q, k, v, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Dispatch to the configured attention impl.

    Pallas kernels have no SPMD partitioning rule, so under a mesh the flash
    path runs inside shard_map (batch over (dp,fsdp), heads over tp); ring /
    ulysses manage the 'sp' axis themselves.
    """
    impl, scale = cfg.attn_impl, _sm_scale(cfg)
    if mesh is None:
        # Ring/ulysses degenerate to plain attention on one device.
        if impl == "flash":
            return flash_attention(q, k, v, causal=True, sm_scale=scale)
        k, v = repeat_kv_heads(q, k, v)
        return mha_reference(q, k, v, causal=True, sm_scale=scale)
    if impl == "ring":
        return ring_attention(q, k, v, causal=True, sm_scale=scale, mesh=mesh)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, causal=True, sm_scale=scale,
                                 mesh=mesh)
    if impl == "reference":
        return mha_reference(q, k, v, causal=True, sm_scale=scale)
    # flash under a mesh: pallas has no SPMD partitioning rule, so run the
    # kernel per-shard: batch over (dp,fsdp), heads over tp, seq replicated.
    # Manual over EVERY mesh axis — the TPU lowering refuses a Mosaic
    # kernel in a region that leaves any axis to the partitioner.
    from ray_tpu.parallel.sharding import manual_shard_map
    k, v = repeat_kv_heads(q, k, v)
    spec = P((AXIS_DP, AXIS_FSDP), None, AXIS_TP, None)
    fn = manual_shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True,
                                           sm_scale=scale),
        set(mesh.axis_names), in_specs=(spec, spec, spec),
        out_specs=spec, mesh=mesh)
    return fn(q, k, v)


def _attention_sp_manual(q, k, v, cfg: LlamaConfig):
    """Attention inside an already-manual 'sp' region (pipeline path):
    call the sharded bodies inline — no nested shard_map."""
    from ray_tpu.ops.ring_attention import _ring_attention_sharded
    from ray_tpu.ops.ulysses import _ulysses_sharded
    k, v = repeat_kv_heads(q, k, v)
    if cfg.attn_impl == "ulysses":
        return _ulysses_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP,
                                use_flash=False)
    return _ring_attention_sharded(q, k, v, _sm_scale(cfg), True, AXIS_SP)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (batch, seq) int32 -> (logits f32 (b, s, vocab), aux_loss).

    Global-view path: call under jit with a mesh context; sharding
    constraints steer XLA's partitioner.  (The pipeline-parallel path is
    ``parallel.pipeline.forward_pipelined`` — manual SPMD.)
    """
    cst = _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        if mesh is not None:
            # One-hot matmul instead of gather: with a ('vocab','embed')-
            # sharded table this lowers to a local matmul + psum over 'tp'
            # — the gather form makes the SPMD partitioner fully
            # rematerialize the table.
            onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
            x = onehot @ params["embed"].astype(cfg.dtype)
        else:
            x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        x = cst(_scaled(x, cfg.embedding_multiplier),
                ("batch", "seq", "embed"))
    x, aux = _scan_layers(params["layers"], x, cfg, mesh, rules)
    return _lm_head(params, x, cfg, cst), _mean_aux(aux, cfg)


def _scaled(x, multiplier: float):
    """``x * multiplier``; a multiplier of 1 adds no op to the program."""
    return x if multiplier == 1.0 else x * multiplier


def _scan_layers(layers, x, cfg: LlamaConfig, mesh, rules,
                 sp_manual: bool = False):
    """The layers over ``x``: one ``lax.scan`` a run of one kind of layer,
    over that run's stacked parameters, each under the layer checkpoint.
    Returns ``(x, aux)``."""
    carry = (x, _zero_aux(cfg))
    for mixer, stacked in _runs(cfg, layers):
        layer_fn = _make_layer_fn(cfg, mesh, rules, sp_manual, mixer)
        if cfg.remat:
            layer_fn = _checkpoint(layer_fn)
        carry, _ = jax.lax.scan(layer_fn, carry, stacked)
    return carry


# What the layer checkpoint keeps of a Mamba layer: the input projection's
# output [z | xBC | dt] (bf16, 139 MB a layer at 8192 tokens).  With it the
# backward pass runs no second ``ssm_in`` matmul; the convolution, the
# scan and the gated norm ARE run again (their intermediates are several
# times that size).  On the v5e: 8.8 ms of a 507 ms step for 1.25 GB held,
# 2.4 GB of program (PERF.md §6, PR 30).
MAMBA_SAVED_RESIDUALS = ("ssm_proj",)


def _checkpoint(layer_fn):
    """The layer checkpoint of every path (``cfg.remat``): the backward
    pass recomputes the layer from its input, except the few residuals
    that are dear to recompute and cheap to hold, named where they are
    made — the flash kernel's output and log-sum-exp, an expert layer's
    row index (its sorts' results), a Mamba layer's input projection.  A
    layer that never produces a name (reference attention, a dense FFN)
    saves nothing under it."""
    return jax.checkpoint(
        layer_fn, policy=jax.checkpoint_policies.save_only_these_names(
            *attention.SAVED_RESIDUALS, *moe.SAVED_RESIDUALS,
            *MAMBA_SAVED_RESIDUALS))


def _zero_aux(cfg: LlamaConfig):
    """What the layer scan carries beside the activations: a dense model's
    auxiliary loss (0), or the expert layers' float32 scalars."""
    zero = jnp.zeros((), jnp.float32)
    if not cfg.num_experts:
        return zero
    return {"aux_loss": zero, "z_loss": zero, "load_max_over_mean": zero,
            "dropped": zero}


def _mean_aux(aux, cfg: LlamaConfig):
    if not cfg.num_experts:
        return aux / cfg.num_layers
    return dict(aux, aux_loss=aux["aux_loss"] / cfg.num_layers,
                z_loss=aux["z_loss"] / cfg.num_layers)


def _moe(x, lp, cfg: LlamaConfig, mesh: Optional[Mesh], cst):
    """The expert layer (``ops.moe.moe_block``) on the residual stream.
    Under a mesh it runs per shard, as the flash kernel does: tokens over
    (dp, fsdp) x sp, experts over ep, their width over tp, partial outputs
    summed over ep x tp.  Inside a region that is already manual (the
    pipeline) it is called as it is and the partitioner splits it, which
    the TPU lowering refuses for a Mosaic kernel."""
    block = functools.partial(
        moe_block, num_selected=cfg.num_selected, norm_eps=cfg.norm_eps,
        norm_topk_prob=cfg.norm_topk_prob)
    args = (x, lp["mlp_norm"], lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"])
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return block(*args)
    from ray_tpu.parallel.sharding import manual_shard_map
    # The parameters as the region takes them, laid out under the scope
    # that uses them (pinned first as they are stored, or the partitioner
    # moves the change of layout up to the scan's slice): the fsdp gathers
    # and their gradients' scatters then carry a step scope like every
    # other collective.
    axes = param_logical_axes(cfg)["layers"]

    def laid_out(name, *gathered):
        return cst(cst(lp[name], axes[name][1:]), gathered)

    with jax.named_scope("moe_route"):
        small = (laid_out("mlp_norm", None), laid_out("router", None, None))
    with jax.named_scope("moe_experts"):
        args = (x,) + small + (
            laid_out("w_gate", "expert", None, "mlp"),
            laid_out("w_up", "expert", None, "mlp"),
            laid_out("w_down", "expert", "mlp", None))
    x_spec = P((AXIS_DP, AXIS_FSDP), AXIS_SP, None)
    up_spec = P(AXIS_EP, None, AXIS_TP)
    fn = manual_shard_map(
        functools.partial(block, token_axes=(AXIS_DP, AXIS_FSDP, AXIS_SP),
                          expert_axis=AXIS_EP, sum_axes=(AXIS_EP, AXIS_TP)),
        set(mesh.axis_names),
        in_specs=(x_spec, P(), P(), up_spec, up_spec,
                  P(AXIS_EP, AXIS_TP, None)),
        out_specs=(x_spec, P()), mesh=mesh)
    return fn(*args)


def _lm_head(params, x, cfg: LlamaConfig, cst):
    """Final norm and head product -> f32 logits (scope ``lm_head``)."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:  # one table, read twice: its gradient is
            # the sum of both uses
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(cfg.dtype))
        else:
            logits = x @ params["lm_head"].astype(cfg.dtype)
        logits = _scaled(logits.astype(jnp.float32),
                         1.0 / cfg.logits_scaling)
        return cst(logits, ("batch", "seq", "vocab"))


def _make_cst(mesh, rules):
    if mesh is None:
        return lambda x, ax: x
    return lambda x, ax: with_logical_constraint(x, ax, mesh=mesh,
                                                 rules=rules)


def _attention_mixer(x, lp, cfg: LlamaConfig, mesh, cst, sp_manual):
    """Softmax attention on the residual stream (scopes ``attn_qkv``,
    ``attention``, ``attn_out``)."""
    b, s = x.shape[0], x.shape[1]
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = h @ lp["wq"].astype(cfg.dtype)
        k = h @ lp["wk"].astype(cfg.dtype)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(cfg.dtype)).reshape(
            b, s, cfg.num_kv_heads, cfg.head_dim)
        if cfg.position_embedding == "rope":
            offset = 0
            if sp_manual:
                offset = jax.lax.axis_index(AXIS_SP) * s
            cos, sin = rope(s, cfg.head_dim, cfg.rope_theta, offset=offset)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q = cst(q, ("batch", "seq", "heads", "head_dim"))
        k = cst(k, ("batch", "seq", "kv_heads", "head_dim"))
    with jax.named_scope("attention"):
        if sp_manual:
            o = _attention_sp_manual(q, k, v, cfg)
        else:
            o = _attention(q, k, v, cfg, mesh)
    with jax.named_scope("attn_out"):
        o = o.reshape(b, s, cfg.qkv_dim)
        return x + _scaled(cst(o @ lp["wo"].astype(cfg.dtype),
                               ("batch", "seq", "embed")),
                           cfg.residual_multiplier)


def _mamba_mixer(x, lp, cfg: LlamaConfig, mesh, cst, sp_manual):
    """A Mamba-2 mixer on the residual stream (``ops/ssm.py``): scopes
    ``ssm_in`` (norm, the one input projection, its split), ``ssm_conv``
    (the convolution over x, B, C with its SiLU; dt's softplus),
    ``ssm_scan`` (the chunked scan, ``D x`` included), ``ssm_out`` (the
    norm of the GATED output — gate first, then one norm over the whole
    inner width —, the output projection, the residual add).  The scan is
    Pallas kernels where its shapes allow, per shard of the batch."""
    b, s = x.shape[0], x.shape[1]
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        h = rms_norm(x, lp["ssm_norm"], cfg.norm_eps)
        zxbcdt = checkpoint_name(h @ lp["ssm_in"].astype(cfg.dtype),
                                 *MAMBA_SAVED_RESIDUALS)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + cfg.ssm_conv_dim], -1)
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv1d(xbc, lp["conv_w"], lp["conv_b"])
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], -1)
    with jax.named_scope("ssm_scan"):
        y = _ssd_scan(mesh, sp_manual)(
            xs.reshape(b, s, cfg.ssm_heads, cfg.ssm_head_dim), dt,
            -jnp.exp(lp["A_log"].astype(f32)),
            bm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            cm.reshape(b, s, cfg.ssm_groups, cfg.ssm_state),
            lp["D"], chunk=cfg.ssm_chunk)
    with jax.named_scope("ssm_out"):
        y = gated_rms_norm(y.reshape(b, s, inner), z, lp["gate_norm"],
                           cfg.norm_eps)
        return x + _scaled(cst(y @ lp["ssm_out"].astype(cfg.dtype),
                               ("batch", "seq", "embed")),
                           cfg.residual_multiplier)


def _dense_ffn(x, aux, lp, cfg: LlamaConfig, mesh, cst):
    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = h @ lp["w_gate"].astype(cfg.dtype)
        up = h @ lp["w_up"].astype(cfg.dtype)
        ff = swiglu(gate, up) @ lp["w_down"].astype(cfg.dtype)
        return x + _scaled(cst(ff, ("batch", "seq", "embed")),
                           cfg.residual_multiplier), aux


def _moe_ffn(x, aux, lp, cfg: LlamaConfig, mesh, cst):
    # opens its own four scopes in place of ffn
    x, stats = _moe(x, lp, cfg, mesh, cst)
    x = cst(x, ("batch", "seq", "embed"))
    return x, {k: (jnp.maximum if k == "load_max_over_mean"
                   else jnp.add)(v, stats[k]) for k, v in aux.items()}


_MIXERS = {"attention": _attention_mixer, "mamba": _mamba_mixer}


def _make_layer_fn(cfg: LlamaConfig, mesh, rules, sp_manual: bool = False,
                   mixer: str = "attention"):
    """One layer as a scan body over stacked layer params: a mixer
    (``_MIXERS``) then an FFN (dense, or the expert layer), each adding to
    the residual stream.  Shapes are read off the activation so the same
    body serves the full batch (forward) and microbatches
    (forward_pipelined).

    ``sp_manual``: the body runs inside a shard_map that is manual over
    'sp' (the pipeline path — jax/shardy cannot nest manual regions): the
    seq dim is device-local, RoPE uses the rank's global offset, and
    ring/ulysses attention run inline over the bound 'sp' axis.
    """
    cst = _make_cst(mesh, rules)
    mix = _MIXERS[mixer]
    ffn = _moe_ffn if cfg.num_experts else _dense_ffn

    def layer_fn(carry, lp):
        x, aux = carry
        x = mix(x, lp, cfg, mesh, cst, sp_manual)
        return ffn(x, aux, lp, cfg, mesh, cst), None

    return layer_fn


def _one_kind(cfg: LlamaConfig, what: str) -> None:
    if len(cfg.layer_runs) > 1:
        raise NotImplementedError(
            f"{what} splits ONE stack of layers into stages; this model's "
            f"layers differ ({cfg.layer_runs}): train it with "
            "make_train_step(pipelined=False)")


def forward_pipelined(params: Dict[str, Any], tokens: jax.Array,
                      cfg: LlamaConfig, *, mesh: Mesh,
                      num_microbatches: int,
                      rules: Optional[LogicalAxisRules] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward: transformer layers split into ``pp``
    stages (parallel.pipeline), embed/head replicated across stages.

    Sequence parallelism composes: with attn_impl ring/ulysses the pipeline
    region is manual over {'pp','sp'} (jax/shardy cannot *nest* manual
    regions) — activations enter seq-sharded, RoPE offsets come from the
    'sp' rank, and attention runs inline over the bound axis.

    The expert layers' auxiliary losses and counters are not carried out of
    the pipeline stages (stage outputs must be activation-shaped): under pp
    an MoE model trains with both coefficients at 0.
    """
    from ray_tpu.parallel.pipeline import pipeline_apply, split_stages
    from ray_tpu.parallel.mesh import AXIS_PP

    _one_kind(cfg, "forward_pipelined")
    cst = _make_cst(mesh, rules)
    with jax.named_scope("embed"):
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
        x = cst(_scaled(onehot @ params["embed"].astype(cfg.dtype),
                        cfg.embedding_multiplier),
                ("batch", "seq", "embed"))

    sp_manual = cfg.attn_impl in ("ring", "ulysses") and \
        mesh.shape[AXIS_SP] > 1
    if sp_manual:
        # Inside the manual region 'seq' is device-local and 'sp' is bound:
        # strip it from the rules GSPMD sees.
        inner_rules = dict(rules if rules is not None else DEFAULT_RULES)
        inner_rules["seq"] = None
        x_spec = P(None, AXIS_SP, None)
        manual_axes = {AXIS_PP, AXIS_SP}
    else:
        inner_rules = rules
        x_spec = P()
        manual_axes = {AXIS_PP}
    def stage_fn(stage_params, x_mb):
        return _scan_layers(stage_params, x_mb, cfg, mesh, inner_rules,
                            sp_manual)[0]

    stages = split_stages(params["layers"], mesh.shape[AXIS_PP])
    x = pipeline_apply(stage_fn, stages, x, mesh=mesh,
                       num_microbatches=num_microbatches,
                       manual_axes=manual_axes, x_spec=x_spec)
    return _lm_head(params, x, cfg, cst), _zero_aux(cfg)


def pipeline_stage_params(params: Dict[str, Any],
                          num_stages: int) -> list:
    """Stage-sliced construction for the ACTOR pipeline
    (``train.pipeline_actors``): split the stacked layer params into
    ``num_stages`` contiguous slices, folding the embedding into stage
    0 and the final norm + LM head into the last stage — each stage
    actor then owns exactly its stage's tensors, nothing replicated."""
    layers = params["layers"]
    if not isinstance(layers, dict) or "lm_head" not in params:
        raise NotImplementedError(
            "the actor pipeline splits one stack of layers and gives the "
            "embedding and the head to different stages: not a model whose "
            "layers differ, nor one with a tied head")
    n_layers = next(iter(layers.values())).shape[0]
    if n_layers % num_stages:
        raise ValueError(
            f"{n_layers} layers not divisible by {num_stages} stages")
    per = n_layers // num_stages
    out = []
    for s in range(num_stages):
        sp: Dict[str, Any] = {
            "layers": {k: v[s * per:(s + 1) * per]
                       for k, v in layers.items()}}
        if s == 0:
            sp["embed"] = params["embed"]
        if s == num_stages - 1:
            sp["final_norm"] = params["final_norm"]
            sp["lm_head"] = params["lm_head"]
        out.append(sp)
    return out


def make_pipeline_stage_fn(cfg: LlamaConfig):
    """The uniform per-stage callable for ``train.pipeline_actors``:
    embeds on the stage holding ``embed`` (its input is then raw
    tokens), scans the stage's layer slice, and projects to logits on
    the stage holding ``lm_head``.  Key presence is trace-time static,
    so each stage jits to exactly its own program."""

    _one_kind(cfg, "make_pipeline_stage_fn")

    def stage_fn(sp, x):
        if "embed" in sp:
            with jax.named_scope("embed"):
                x = _scaled(jnp.take(sp["embed"], x, axis=0).astype(
                    cfg.dtype), cfg.embedding_multiplier)
        x, _ = _scan_layers(sp["layers"], x, cfg, None, None)
        if "lm_head" in sp:
            x = _lm_head(sp, x, cfg, _make_cst(None, None))
        return x

    return stage_fn


def make_pipeline_loss_fn(cfg: LlamaConfig):
    """Next-token cross-entropy over the last stage's logits — the
    same mean-NLL ``loss_fn`` computes, as a ``(logits, targets)``
    pair for the actor pipeline's loss stage."""

    def pipeline_loss(logits, targets):
        with jax.named_scope("loss"):
            return _mean_nll(logits, targets)

    return pipeline_loss


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: LlamaConfig, *, mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None,
            forward_fn=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Next-token cross-entropy.  batch: {"tokens": (b, s+1) int32} or
    {"inputs": (b, s), "targets": (b, s)}; returns (loss, metrics).

    ``forward_fn(params, inputs) -> (logits, aux)`` overrides the forward
    pass (e.g. the pipelined path) so there is exactly one loss definition.
    """
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    if forward_fn is None:
        logits, aux = forward(params, inputs, cfg, mesh=mesh, rules=rules)
    else:
        logits, aux = forward_fn(params, inputs)
    with jax.named_scope("loss"):
        loss = _mean_nll(logits, targets)
        if not cfg.num_experts:
            total = loss + cfg.aux_loss_coef * aux
            return total, {"loss": loss, "aux_loss": aux,
                           "perplexity": jnp.exp(loss)}
        total = (loss + cfg.aux_loss_coef * aux["aux_loss"]
                 + cfg.z_loss_coef * aux["z_loss"])
        return total, {"loss": loss, "aux_loss": aux["aux_loss"],
                       "z_loss": aux["z_loss"],
                       "moe_load_max_over_mean": aux["load_max_over_mean"],
                       "moe_dropped": aux["dropped"],
                       "perplexity": jnp.exp(loss)}


def _mean_nll(logits, targets):
    if logits.shape[0] == 1:
        # One row: drop the degenerate dimension.  With it XLA's TPU
        # compiler turns the gradient of the gather below into a FLAT
        # scatter — float32 zeros the size of the logits, a relayout of
        # them and 5 GB more of temporaries at 8192 x 100352 (PERF.md §6,
        # PR 30).  Batches of several rows compile as they always have.
        logits, targets = logits[0], targets[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def _ssd_scan(mesh: Optional[Mesh], sp_manual: bool):
    """``ssd_chunked`` as ``_mamba_mixer`` calls it.  A Pallas kernel has
    no partitioning rule, so under a mesh the scan runs per shard of the
    batch, manual over EVERY axis as ``_attention`` runs flash: ``ssm_inner``
    maps to no mesh axis, so a shard holds whole heads and whole
    sequences.  Inside an already-manual region it is called inline."""
    if mesh is None or sp_manual:
        return ssd_chunked
    from ray_tpu.parallel.sharding import manual_shard_map

    def rows(ndim):
        return P((AXIS_DP, AXIS_FSDP), *(None,) * (ndim - 1))

    def per_shard(x, dt, a, bm, cm, d, *, chunk):
        return manual_shard_map(
            lambda *t: ssd_chunked(*t, chunk=chunk), set(mesh.axis_names),
            in_specs=(rows(4), rows(3), P(), rows(4), rows(4), P()),
            out_specs=rows(4), mesh=mesh)(x, dt, a, bm, cm, d)

    return per_shard
