"""Trainers: the user-facing fit() entry points.

Reference: ``python/ray/train/base_trainer.py:52`` (``fit`` :538) and
``data_parallel_trainer.py:56``.  The reference wraps every trainer into a
Tune Trainable (:663) so fit == a single Tune trial; here fit() drives the
BackendExecutor directly and the Tune layer (ray_tpu.tune) wraps trainers
the same way via ``as_trainable`` for HPO.

``JaxTrainer`` is the TorchTrainer-equivalent: SPMD data-parallel training
where each worker is one JAX process owning its TPU chips, the collective
backend is jax.distributed + XLA (train/backend.py), and the in-worker
step is a pjit-ed mesh program (train/core.py).

Fault tolerance matches the reference (``FailureConfig(max_failures)``,
``backend_executor.py:522,583``): on worker failure the whole gang is torn
down and restarted from the latest reported checkpoint — elastic restart,
slice-granular, which is the only sane recovery unit on TPU (a chip failure
kills the slice; SURVEY.md §7 hard-part 5).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.train.backend import JaxConfig
from ray_tpu.train.backend_executor import BackendExecutor, TrainingFailedError
from ray_tpu.util import tracing


class BaseTrainer:
    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        raise NotImplementedError

    def as_trainable(self):
        """Wrap into a Tune trainable (reference: base_trainer.py:663)."""
        trainer = self

        def train_func(config):
            t = trainer._with_config_overrides(config)
            result = t.fit()
            return result.metrics

        return train_func

    def _with_config_overrides(self, config: Dict[str, Any]):
        return self


class DataParallelTrainer(BaseTrainer):
    """Reference: python/ray/train/data_parallel_trainer.py:56."""

    def __init__(self, train_loop_per_worker: Callable[[Dict[str, Any]], None],
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 backend_config: Optional[JaxConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        super().__init__(scaling_config=scaling_config,
                         run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint)
        self._train_fn = train_loop_per_worker
        self._train_config = train_loop_config or {}
        self._backend_config = backend_config or JaxConfig()
        self._datasets = datasets or {}

    def fit(self) -> Result:
        """Run the loop on the gang.  ``Result.metrics["_spans"]`` says
        where the time of this call went: per span name (``train.*`` of
        the driver, the head's ``sched.wait`` / ``worker.spawn`` of the
        workers, rank 0's ``train.session_start``, ``train.loop`` and
        ``session.report``; of a worker that was granted chips,
        ``device.bring_up`` with its ``jax.import`` and
        ``jax.backend_init``, between ``train.session_start`` and
        ``train.loop``: ``train/backend.py::bring_up``; from there on,
        or where a CPU worker's loop builds train steps, JAX's
        ``jax.trace``, ``jax.lower``, ``jax.compile``, ``jax.cache_load``,
        ``jax.cache_miss`` of every program, and the process-wide
        ``gc.pause`` and ``host.lag``: ``tracing.watch_process``; the
        worker's periodic thread where an iteration took over a
        millisecond, ``worker.flush``)
        ``count``, ``total_s``, ``max_s``, ``first_start``, ``last_end``
        and ``recent``, the ``(start, end)`` of its last 256 spans;
        ``session.report`` also ``clock``, the loop thread's CPU clocks
        and context switches at each of those (``tracing.thread_clock``:
        which step came late, and whether the worker was stopped, busy or
        waiting, is ``benchmark/late_steps.py``'s to say).  Every
        entry of ``metrics_history`` carries ``_timestamp`` (the start of
        its ``session.report``) and ``_time_this_iter_s`` (since the
        report before it) beside ``_training_iteration``."""
        with tracing.collect() as got, tracing.span("train.fit"):
            result = self._fit()
        got.merge(result.metrics.get("_spans"))
        got.add_caused()
        result.metrics["_spans"] = got.summary
        return result

    def _fit(self) -> Result:
        failure = self.run_config.failure_config or FailureConfig()
        retries = failure.max_failures
        checkpoint = self.resume_from_checkpoint
        last_error: Optional[BaseException] = None
        while True:
            executor = BackendExecutor(self._backend_config,
                                       self.scaling_config)
            try:
                executor.start()
                config = dict(self._train_config)
                if self._datasets:
                    config["__datasets__"] = {
                        k: _shard_dataset(d, self.scaling_config.num_workers)
                        for k, d in self._datasets.items()}
                payloads = executor.run_training(self._train_fn, config,
                                                 checkpoint)
                return _payloads_to_result(payloads)
            except TrainingFailedError as e:
                last_error = e
                # Group restart from the latest checkpoint streamed before
                # the death (reference: backend_executor.py:522
                # get_with_failure_handling + the session result queue).
                if executor.latest_checkpoint is not None:
                    checkpoint = executor.latest_checkpoint
                if retries == 0:
                    return Result(metrics={}, checkpoint=checkpoint,
                                  error=e)
                if retries > 0:
                    retries -= 1
            finally:
                executor.shutdown()


def _shard_dataset(dataset, num_shards: int):
    if hasattr(dataset, "split"):
        return dataset.split(num_shards)
    return [dataset] * num_shards


def _payloads_to_result(payloads) -> Result:
    rank0 = payloads[0]
    reports = rank0["reports"]
    ckpt = None
    if rank0["checkpoints"]:
        ckpt = Checkpoint.from_bytes(rank0["checkpoints"][-1])
    # A copy: ``_spans`` is filled in once, here and by ``fit()`` — it is
    # no part of the last report in ``metrics_history``.
    metrics = dict(reports[-1]) if reports else {}
    metrics["_spans"] = rank0.get("spans")
    return Result(metrics=metrics, checkpoint=ckpt,
                  metrics_history=reports)


class JaxTrainer(DataParallelTrainer):
    """The TorchTrainer-equivalent for TPU (reference seam:
    python/ray/train/torch/torch_trainer.py + torch/config.py:29).

    The collective plane is jax.distributed/XLA — there is nothing like
    ``prepare_model`` to wrap: the user loop builds a mesh over the global
    devices (``jax.devices()`` spans the gang after rendezvous) and jits a
    sharded step; see ray_tpu.train.core.make_train_step.
    """
