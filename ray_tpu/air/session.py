"""Worker-side training session API (reference: python/ray/air/session.py:43
and python/ray/train/_internal/session.py:63).

Inside ``train_loop_per_worker`` user code calls::

    from ray_tpu.air import session
    session.report({"loss": ...}, checkpoint=Checkpoint.from_dict(...))
    session.get_world_rank(); session.get_checkpoint()

Reports accumulate in the active session and are returned to the driver by
the worker actor when the loop finishes (the driver-side streaming queue of
the reference is a round-2 item; Tune-style mid-training coordination uses
the iterative Trainable API instead).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.util import tracing

_local = threading.local()


class _TrainSession:
    def __init__(self, world_rank: int = 0, world_size: int = 1,
                 local_rank: int = 0,
                 checkpoint: Optional[Checkpoint] = None,
                 trial_info: Optional[Dict[str, Any]] = None,
                 stream_topic: Optional[str] = None):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.loaded_checkpoint = checkpoint
        self.trial_info = trial_info or {}
        self.stream_topic = stream_topic
        self.reports: List[Dict[str, Any]] = []
        self.checkpoints: List[Checkpoint] = []
        self._last_report = time.time()  # the first interval's start

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None):
        # clock=True: the loop thread's CPU clocks and scheduler counters
        # at every report, so a late one says whose fault it was
        # (``_spans["session.report"]["clock"]``).
        with tracing.span("session.report", clock=True) as s:
            self._report(metrics, checkpoint, s.start)

    def _report(self, metrics: Dict[str, Any],
                checkpoint: Optional[Checkpoint], now: float):
        # Auto-filled as the reference's session fills its results
        # (train/_internal/session.py): the host's clock of every report,
        # on the clock of the spans (``now`` is this report's span's start).
        entry = dict(metrics)
        entry["_training_iteration"] = len(self.reports)
        entry["_timestamp"] = now
        entry["_time_this_iter_s"] = now - self._last_report
        self._last_report = now
        self.reports.append(entry)
        if checkpoint is not None:
            self.checkpoints.append(checkpoint)
        if self.stream_topic is not None:
            # Live-stream to the driver so mid-training checkpoints survive
            # worker death (reference: the session result queue,
            # train/_internal/session.py:322).
            try:
                from ray_tpu._private.worker_main import get_worker_runtime
                rt = get_worker_runtime()
                if rt is not None:
                    import pickle
                    # Only rank 0 ships checkpoint bytes — the driver
                    # keeps rank 0's anyway, other ranks' would be
                    # serialized and dropped.
                    ship = (checkpoint is not None
                            and self.world_rank == 0)
                    payload = pickle.dumps({
                        "rank": self.world_rank,
                        "metrics": entry,
                        "checkpoint": (checkpoint.to_bytes()
                                       if ship else None),
                    })
                    rt.publish_event(self.stream_topic, payload)
            except Exception:
                pass  # streaming is best-effort; end-of-run return is exact


def _set_session(s: Optional[_TrainSession]):
    _local.session = s


def _get_session() -> Optional[_TrainSession]:
    return getattr(_local, "session", None)


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    s = _get_session()
    if s is None:
        raise RuntimeError("session.report() outside a train session")
    s.report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    s = _get_session()
    return s.loaded_checkpoint if s else None


def get_world_rank() -> int:
    s = _get_session()
    return s.world_rank if s else 0


def get_world_size() -> int:
    s = _get_session()
    return s.world_size if s else 1


def get_local_rank() -> int:
    s = _get_session()
    return s.local_rank if s else 0


def get_trial_name() -> Optional[str]:
    s = _get_session()
    return s.trial_info.get("name") if s else None
