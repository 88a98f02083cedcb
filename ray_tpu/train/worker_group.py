"""WorkerGroup: a gang of training-worker actors.

Reference: ``python/ray/train/_internal/worker_group.py:92`` — N actors
created from one ``RayTrainWorker`` class, ``execute``/``execute_async``
running a function on every worker.  TPU difference: each worker owns
``tpu_chips_per_worker`` chips (the scheduler pins ``TPU_VISIBLE_CHIPS``
before the worker's first jax import), so a worker is "one JAX process on
one TPU host" and in-worker collectives ride ICI.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import ray_tpu as ray
from ray_tpu._private import device_env
from ray_tpu.remote_function import _bulk_submit
from ray_tpu.util.placement_group import PlacementGroup


@ray.remote
class TrainWorker:
    """Reference: RayTrainWorker (worker_group.py:40)."""

    def __init__(self, metadata: Dict[str, Any]):
        self._metadata = metadata
        self._env: Dict[str, str] = {}

    def set_env(self, env: Dict[str, str]):
        import os
        self._env.update(env)
        os.environ.update(env)
        return True

    def get_metadata(self):
        import os
        import socket
        return {
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "tpu_chips": ",".join(map(str, device_env.granted_chips())),
        }

    def execute(self, fn: Callable, *args, **kwargs):
        return fn(*args, **kwargs)

    def run_train_fn(self, train_fn: Callable, config: Dict[str, Any],
                     session_kwargs: Dict[str, Any],
                     worker_setup: Optional[Callable] = None):
        """Run the user loop under an active air session; return the
        session's reports + checkpoints (driver-side aggregation).
        A process that was granted chips first runs the backend's
        ``worker_setup`` on them (``train/backend.py::bring_up``); one
        that raises fails the call before the loop."""
        from ray_tpu.util import tracing
        with tracing.collect() as got:
            # The first import of ray_tpu.air in this process brings
            # numpy (0.3 s on the v5e host, PERF.md PR 23).
            with tracing.span("train.session_start"):
                from ray_tpu.air.session import _TrainSession, _set_session
                sess = _TrainSession(**session_kwargs)
                _set_session(sess)
            try:
                chips = device_env.granted_chips()
                if worker_setup is not None and chips:
                    worker_setup(chips)
                with tracing.span("train.loop"):
                    train_fn(config)
            finally:
                _set_session(None)
        ckpt_blobs = [c.to_bytes() for c in sess.checkpoints]
        return {"reports": sess.reports, "checkpoints": ckpt_blobs,
                "spans": got.summary}


class WorkerGroup:
    def __init__(self, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 placement_group: Optional[PlacementGroup] = None):
        self.num_workers = num_workers
        self._workers = []
        for i in range(num_workers):
            opts = {"resources": dict(resources_per_worker)}
            cpu = opts["resources"].pop("CPU", 1.0)
            tpu = opts["resources"].pop("TPU", 0.0)
            kw = {"num_cpus": cpu, "num_tpus": int(tpu),
                  "resources": opts["resources"] or None}
            if placement_group is not None:
                from ray_tpu.util.scheduling_strategies import (
                    PlacementGroupSchedulingStrategy,
                )
                kw["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    placement_group=placement_group,
                    placement_group_bundle_index=i)
            self._workers.append(
                TrainWorker.options(**kw).remote({"rank": i}))

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return ray.get(self.execute_async(fn, *args, **kwargs))

    def execute_async(self, fn: Callable, *args, **kwargs):
        # Bulk path: one runtime submission for the whole worker group.
        return _bulk_submit([(w.execute, (fn,) + args, kwargs)
                             for w in self._workers])

    def execute_single(self, index: int, fn: Callable, *args, **kwargs):
        return ray.get(self._workers[index].execute.remote(fn, *args,
                                                           **kwargs))

    @property
    def workers(self):
        return list(self._workers)

    def shutdown(self):
        for w in self._workers:
            try:
                ray.kill(w)
            except Exception:
                pass
        self._workers = []
