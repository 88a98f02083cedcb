"""The head's span ``sched.wait`` of the trainer's worker: its actor
creation submitted -> dispatched to a process.  Holds the wait for
resources and for the chips of a worker that is still retiring.  From
``Result.metrics["_spans"]`` (``ray_tpu.util.tracing``); with several
workers, the one that waited longest."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "sched.wait" not in spans:
        return None
    return spans["sched.wait"]["max_s"]
