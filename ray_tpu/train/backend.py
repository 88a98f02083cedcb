"""Collective backends: how a worker gang becomes one SPMD program.

Reference seam: ``python/ray/train/torch/config.py:148`` — ``_TorchBackend
.on_start`` runs ``dist.init_process_group('nccl', tcp://rank0)`` on every
worker (SURVEY.md §2.3 calls this "the exact seam the TPU build replaces").

Here the backend is JAX: rank 0 publishes a coordinator address; every
worker calls ``jax.distributed.initialize(coordinator, n, rank)`` and the
global device mesh spans all workers' chips — collectives are XLA over
ICI (in-host) / DCN (cross-host), no NCCL-style library in sight.

Who opens the chips, and when: the worker that was granted them, itself,
before the user's loop (``bring_up``, the backend's ``worker_setup``,
which ``TrainWorker.run_train_fn`` runs between ``train.session_start``
and ``train.loop`` in a process with a non-empty ``TPU_VISIBLE_CHIPS``).
Its spans are ``device.bring_up`` (``chips=``) > ``jax.import``,
``jax.backend_init``; between the two ``tracing.watch_process()`` starts,
so JAX's pipeline is watched from the first program the process makes;
then ``check_devices``: the platform is ``tpu`` and the local devices
are as many as the chips granted, or ``fit()`` fails before the loop.
The loop's own ``import jax`` / ``jax.devices()`` are then cached calls.
A CPU worker runs none of it and imports no JAX.  In a gang ``on_start``'s
``jax.distributed.initialize`` comes first, as it must (its reply's
``device_count`` starts the runtime there: under the driver's
``train.backend_start``, and the worker's own spans are short).
"""

from __future__ import annotations

import socket
from typing import Any, Callable, Dict, Optional, Sequence


class Backend:
    """Plugin interface (reference: train/backend.py BackendConfig/Backend)."""

    # Per-worker set-up: a module-level function of the chips the
    # process was granted (``device_env.granted_chips()``), or None.
    # Runs in every worker that was granted chips, before the loop.
    worker_setup: Optional[Callable[[Sequence[int]], None]] = None

    def on_start(self, worker_group, backend_config) -> None:
        pass

    def on_shutdown(self, worker_group, backend_config) -> None:
        pass


class JaxConfig:
    """Backend config for JAX SPMD training.

    distributed=False runs each worker as an independent JAX process (unit
    tests, single worker); True wires jax.distributed across the gang.
    """

    def __init__(self, distributed: Optional[bool] = None,
                 coordinator_port: int = 0):
        self.distributed = distributed
        self.coordinator_port = coordinator_port

    @property
    def backend_cls(self):
        return _JaxBackend


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _init_jax_distributed(coordinator: str, num_processes: int,
                          process_id: int):
    import jax
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id)
    return {"process_index": jax.process_index(),
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count()}


def check_devices(devices: Sequence[Any], chips: Sequence[int]) -> None:
    """The process opened what it was granted: ``devices`` (its local
    devices) are TPU chips, as many as ``chips``."""
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"] or len(devices) != len(chips):
        raise RuntimeError(
            f"this worker was granted {len(chips)} TPU chip(s) "
            f"({','.join(map(str, chips))}) and opened {len(devices)} "
            f"device(s) of platform {'/'.join(platforms) or 'none'}")


def bring_up(chips: Sequence[int]) -> None:
    """Open the chips this process was granted (the module's header)."""
    from ray_tpu.util import tracing

    with tracing.span("device.bring_up", chips=len(chips)):
        with tracing.span("jax.import"):
            import jax
        tracing.watch_process()
        with tracing.span("jax.backend_init"):
            devices = jax.local_devices()  # starts the TPU runtime
        check_devices(devices, chips)


class _JaxBackend(Backend):
    """Reference analog: _TorchBackend (train/torch/config.py:103)."""

    worker_setup = staticmethod(bring_up)

    def on_start(self, worker_group, backend_config: JaxConfig):
        n = worker_group.num_workers
        distributed = backend_config.distributed
        if distributed is None:
            distributed = n > 1
        if not distributed:
            return
        # Rank 0's host runs the coordination service, so hostname AND a
        # free port must both be probed on rank 0's machine (reference: TCP
        # rendezvous on rank-0, train/torch/config.py:113).
        fixed = backend_config.coordinator_port

        def _rendezvous_addr():
            import socket as s
            host = s.gethostname()
            if fixed:
                return f"{host}:{fixed}"
            sock = s.socket()
            sock.bind(("", 0))
            port = sock.getsockname()[1]
            sock.close()
            return f"{host}:{port}"

        coordinator = worker_group.execute_single(0, _rendezvous_addr)
        import ray_tpu as ray
        futs = [
            w.execute.remote(_init_jax_distributed, coordinator, n, rank)
            for rank, w in enumerate(worker_group.workers)
        ]
        infos = ray.get(futs, timeout=120)
        counts = {i["device_count"] for i in infos}
        if len(counts) != 1:
            raise RuntimeError(f"inconsistent global device counts: {infos}")

    def on_shutdown(self, worker_group, backend_config):
        def _shutdown():
            try:
                import jax
                jax.distributed.shutdown()
            except Exception:
                pass
            return True
        try:
            worker_group.execute(_shutdown)
        except Exception:
            pass
