"""ray_tpu.parallel — parallelism strategies as first-class mesh axes.

The reference delegates multi-device parallelism to out-of-band libraries
(torch.distributed inside Train workers, ``python/ray/train/torch/config.py:113``;
NCCL/Gloo groups in ``python/ray/util/collective/``; JAX model parallelism only
via the Alpa release tests, ``release/alpa_tests/``).  On TPU, parallelism is a
property of the *compiled program*: a ``jax.sharding.Mesh`` over ICI/DCN plus
partition specs, with XLA inserting the collectives.  This package makes that
the framework's first-class layer:

- :mod:`mesh`       — mesh axes (dp, fsdp, ep, pp, sp, tp) and construction.
- :mod:`sharding`   — logical-axis rules -> ``NamedSharding``/``PartitionSpec``.
- :mod:`pipeline`   — GPipe-style pipeline parallelism via shard_map+ppermute.
(``ray.util.collective``-equivalent host-level API lives in
``ray_tpu.util.collective``; in-mesh collectives are ``jax.lax.p*``.)
"""

# Names resolve on first use (PEP 562): a driver that only needs
# ``MeshConfig`` for a ScalingConfig must not import JAX — the chip
# belongs to the workers.
from ray_tpu._private.lazy import lazy_exports

__all__, __getattr__ = lazy_exports(__name__, {
    "ray_tpu.parallel.mesh": (
        "AXIS_DP", "AXIS_FSDP", "AXIS_EP", "AXIS_PP", "AXIS_SP", "AXIS_TP",
        "MESH_AXES", "MeshConfig", "make_mesh", "use_mesh"),
    "ray_tpu.parallel.sharding": (
        "LogicalAxisRules", "DEFAULT_RULES", "logical_to_mesh_axes",
        "named_sharding", "shard_pytree", "with_logical_constraint"),
})
