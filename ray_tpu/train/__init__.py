"""ray_tpu.train — distributed training orchestration (Ray Train equivalent).

Reference: ``python/ray/train/`` (SURVEY.md §2.3) — BaseTrainer/
DataParallelTrainer/BackendExecutor/WorkerGroup, with per-framework collective
backends (``train/torch/config.py:148`` starts NCCL process groups).  The TPU
build replaces that seam with JAX: the "backend" is a mesh + sharded
train step; gradient traffic is XLA collectives over ICI, never an external
library.
"""

# Names resolve on first use (PEP 562): the driver side (trainers,
# executor, worker group) must not import JAX — only ``core`` needs it,
# and that runs in the workers that own the chips.
from ray_tpu._private.lazy import lazy_exports

__all__, __getattr__ = lazy_exports(__name__, {
    "ray_tpu.train.core": (
        "TrainState", "init_train_state", "make_train_step",
        "default_optimizer"),
    "ray_tpu.train.backend": ("Backend", "JaxConfig"),
    "ray_tpu.train.backend_executor": (
        "BackendExecutor", "TrainingFailedError"),
    "ray_tpu.train.trainer": (
        "BaseTrainer", "DataParallelTrainer", "JaxTrainer"),
    "ray_tpu.train.worker_group": ("WorkerGroup",),
    "ray_tpu.train.pipeline_actors": ("PipelineStage", "PipelineTrainer"),
})
