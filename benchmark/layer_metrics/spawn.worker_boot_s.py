"""The head's span ``worker.spawn`` of the trainer's worker: process
launched -> its ``ready`` message (interpreter start, the program's
imports, the dial back to the head).  From ``Result.metrics["_spans"]``;
with several workers, the slowest."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "worker.spawn" not in spans:
        return None
    return spans["worker.spawn"]["max_s"]
