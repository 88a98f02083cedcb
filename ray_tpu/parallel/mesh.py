"""Device mesh construction for TPU pods.

Replaces the reference's process-group bootstrap (NCCL rendezvous in
``python/ray/train/torch/config.py:113``, group management in
``python/ray/util/collective/collective.py:120``) with the XLA-native
equivalent: one global ``jax.sharding.Mesh`` whose axes encode every
parallelism strategy.  Axis order is chosen so the *innermost* (fastest
varying, ICI-adjacent) axes carry the heaviest traffic:

    (dp, fsdp, ep, pp, sp, tp)

- ``tp``   tensor parallelism — per-layer allreduce/allgather every matmul;
           must ride ICI, so it is innermost (adjacent devices).
- ``sp``   sequence/context parallelism — ring attention ppermute traffic.
- ``pp``   pipeline stages — point-to-point activation transfers.
- ``ep``   expert parallelism — all-to-all token routing.
- ``fsdp`` ZeRO-3 parameter sharding — per-step allgather/reduce-scatter.
- ``dp``   pure data parallelism — one gradient psum per step; tolerates DCN,
           so it is outermost (maps to the multi-slice axis on multi-pod).
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Optional, Sequence, Tuple

if TYPE_CHECKING:  # MeshConfig is driver-side data: no JAX at import
    import jax

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_EP = "ep"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_TP = "tp"

MESH_AXES: Tuple[str, ...] = (AXIS_DP, AXIS_FSDP, AXIS_EP, AXIS_PP, AXIS_SP, AXIS_TP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Axis sizes for the global mesh.  ``-1`` on at most one axis means
    "absorb all remaining devices" (like torch DeviceMesh / maxtext).

    The reference's ScalingConfig (``python/ray/air/config.py:80``) carries
    only ``num_workers``/``use_gpu``; a TPU ScalingConfig instead carries a
    MeshConfig — the shape of the parallelism, not just its degree.
    """

    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self, n_devices: int) -> Tuple[int, ...]:
        sizes = [self.dp, self.fsdp, self.ep, self.pp, self.sp, self.tp]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {tuple(sizes)} wants {fixed} devices, have {n_devices}")
        return tuple(sizes)

    @staticmethod
    def auto(n_devices: int,
             prefer: Sequence[str] = (AXIS_TP, AXIS_PP, AXIS_SP, AXIS_EP,
                                      AXIS_FSDP, AXIS_DP)) -> "MeshConfig":
        """Factor ``n_devices`` into powers of two across axes in ``prefer``
        order (innermost-heaviest first) — used by tests and the multi-chip
        dry-run to exercise every axis that fits."""
        sizes = {a: 1 for a in MESH_AXES}
        rest = n_devices
        for axis in prefer:
            if rest % 2 == 0 and rest > 1:
                sizes[axis] = 2
                rest //= 2
        # Any leftover factor (odd or large) goes to dp.
        sizes[AXIS_DP] *= rest
        return MeshConfig(**sizes)


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> jax.sharding.Mesh:
    """Build the global mesh.

    On real TPU hardware ``jax.make_mesh`` lays axes out over the physical
    ICI torus (it calls the device-assignment heuristics that keep inner
    axes on adjacent chips); on the CPU backend used in tests it reshapes
    ``jax.devices()`` row-major, which preserves axis semantics.

    Axes are ``Auto`` (GSPMD propagation): model code steers the partitioner
    with ``with_sharding_constraint`` rather than explicit
    sharding-in-types mode, which would demand out_shardings on every
    ambiguous op (gathers, einsums) throughout model code.
    """
    import jax

    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    sizes = config.sizes(len(devices))
    return jax.make_mesh(
        sizes, MESH_AXES, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(MESH_AXES))


def mesh_axis_size(mesh: jax.sharding.Mesh, axis: str) -> int:
    return mesh.shape[axis]


def use_mesh(mesh: jax.sharding.Mesh):
    """Activate ``mesh`` as the ambient mesh, as a context manager."""
    import jax

    return jax.set_mesh(mesh)
