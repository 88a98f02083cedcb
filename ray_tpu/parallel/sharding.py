"""Logical-axis sharding rules.

Model code names tensor dimensions *logically* ("batch", "embed", "heads",
"expert", ...); one rules table maps logical names to mesh axes.  Swapping
the table re-shards the whole model — DP-only, FSDP, 2D (fsdp x tp), MoE —
without touching model code.  This is the TPU-native replacement for the
reference's per-framework DDP/FSDP wrapping (``prepare_model``,
``python/ray/train/torch/train_loop_utils.py:75``): there the strategy is
baked into wrapper modules; here it is data.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import (
    AXIS_DP, AXIS_EP, AXIS_FSDP, AXIS_PP, AXIS_SP, AXIS_TP,
)

# A logical axis maps to one mesh axis, a tuple of mesh axes (dimension
# sharded over their product), or None (replicated).
MeshAxes = Union[None, str, Tuple[str, ...]]
LogicalAxisRules = Dict[str, MeshAxes]

# The mesh axes a batch's rows are split over: fsdp behaves as extra data
# parallelism for activations while sharding parameters ZeRO-3 style on
# their "embed"-like dimension, and so does ep outside the expert layer:
# the ranks that share a layer's experts each own a part of the tokens, and
# the layer's exchange (``ops/moe.py``) brings a token to its experts.
BATCH_AXES = (AXIS_DP, AXIS_FSDP, AXIS_EP)

# Megatron-style 2D sharding + MoE + sequence parallelism.
DEFAULT_RULES: LogicalAxisRules = {
    "batch": BATCH_AXES,
    "seq": AXIS_SP,               # sequence/context parallelism (ring attn)
    "embed": None,                # activation embed dim stays replicated
    "heads": AXIS_TP,             # attention heads over tensor axis
    "kv_heads": AXIS_TP,
    "head_dim": None,
    "mlp": AXIS_TP,               # ffn hidden: column-parallel then row-parallel
    "vocab": AXIS_TP,             # embedding/vocab-parallel output head
    "kernel_in": AXIS_FSDP,       # ZeRO-3: param input dim over fsdp
    "expert": AXIS_EP,            # MoE experts over expert axis
    "stage": AXIS_PP,             # pipeline stages (stacked-stage layout)
    "layer": None,                # scanned-layer leading dim (non-pipelined)
}


def logical_to_mesh_axes(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[LogicalAxisRules] = None,
) -> P:
    """('batch','seq','embed') -> P(('dp','fsdp','ep'), 'sp', None).

    Mesh axes already consumed by an earlier dimension are dropped (a mesh
    axis can shard at most one dimension of a given tensor) — same contract
    as flax's logical partitioning, re-implemented to stay decoupled from
    flax internals.
    """
    rules = DEFAULT_RULES if rules is None else rules
    used = set()
    out = []
    for name in logical_axes:
        axes = rules.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: Optional[LogicalAxisRules] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_mesh_axes(logical_axes, rules))


def with_logical_constraint(x: jax.Array, logical_axes: Sequence[Optional[str]],
                            mesh: Optional[Mesh] = None,
                            rules: Optional[LogicalAxisRules] = None) -> jax.Array:
    """``lax.with_sharding_constraint`` by logical names.  Under a mesh
    context the PartitionSpec alone suffices (and is the only form valid
    inside a manual region); ``mesh`` is bound explicitly only when no
    context mesh exists — same rule as ``manual_shard_map``."""
    spec = logical_to_mesh_axes(logical_axes, rules)
    if mesh is not None and jax.sharding.get_abstract_mesh().empty:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_pytree(tree: Any, spec_tree: Any, mesh: Mesh,
                 rules: Optional[LogicalAxisRules] = None) -> Any:
    """Device-put a pytree of host arrays according to a matching pytree of
    logical-axis tuples (e.g. from a model's ``param_logical_axes()``)."""
    def _put(x, axes):
        return jax.device_put(x, named_sharding(mesh, *axes, rules=rules))
    return jax.tree.map(_put, tree, spec_tree,
                        is_leaf=lambda x: x is None)


def manual_shard_map(f, axis_names, in_specs, out_specs,
                     mesh: Optional[Mesh] = None):
    """shard_map manual over only ``axis_names`` (other mesh axes stay under
    GSPMD auto-propagation), resolved against the *context* mesh so ops that
    wrap themselves in shard_map (ring attention over 'sp', pipeline over
    'pp') nest inside each other and inside jit.  ``mesh`` is bound
    explicitly only when no context mesh exists (eager/standalone calls,
    or a jitted step built with ``mesh=`` and called outside ``use_mesh``
    — ``jax.set_mesh`` cannot be opened under a trace)."""
    kw = dict(in_specs=in_specs, out_specs=out_specs,
              axis_names=set(axis_names), check_vma=False)
    # Always under jit: partial-manual shard_map only lowers correctly
    # there (eager evaluation — a bare call or one under an un-jitted
    # grad trace — tries to complete out_specs with every mesh axis);
    # nested inside an outer jit it is inlined, so it is free.
    in_ctx = jax.jit(jax.shard_map(f, **kw))
    bound = (jax.jit(jax.shard_map(f, mesh=mesh, **kw))
             if mesh is not None else None)

    def call(*args):
        if bound is not None and jax.sharding.get_abstract_mesh().empty:
            return bound(*args)
        return in_ctx(*args)

    return call


def batch_shard_map(fn, mesh: Mesh, in_ranks, out_ranks, reduce=None):
    """``fn`` per shard of the batch: a Pallas kernel has no partitioning
    rule, so a recurrent mixer's scan runs in a region that is manual over
    EVERY mesh axis (the TPU lowering refuses a Mosaic kernel in one that
    leaves an axis to the partitioner), each operand and output split by
    its leading dimension over ``BATCH_AXES`` and whole in every other.
    ``in_ranks`` and ``out_ranks`` (a tuple where ``fn`` returns one) give
    each one's rank, None for a replicated one; a replicated output is each
    shard's own until ``reduce(value, axis names)`` joins them."""
    def rows(rank):
        if rank is None:
            return P()
        return P(BATCH_AXES, *(None,) * (rank - 1))

    def specs(ranks):
        return (tuple(map(rows, ranks)) if isinstance(ranks, tuple)
                else rows(ranks))

    @functools.wraps(fn)   # the region keeps ``fn``'s name in the name stack
    def joined(*args):
        return tuple(
            out if rank is not None else reduce(out, tuple(mesh.axis_names))
            for out, rank in zip(fn(*args), out_ranks))

    return manual_shard_map(
        fn if reduce is None else joined, set(mesh.axis_names),
        in_specs=specs(tuple(in_ranks)), out_specs=specs(out_ranks),
        mesh=mesh)


def sharding_tree(spec_tree: Any, mesh: Mesh,
                  rules: Optional[LogicalAxisRules] = None) -> Any:
    """Pytree of logical-axis tuples -> pytree of NamedShardings (for jit
    in_shardings/out_shardings)."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, *axes, rules=rules), spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))
