"""The driver's span ``train.backend_start``: ``Backend.on_start``, the
``jax.distributed`` rendezvous of a gang (next to nothing for the one
worker these cells run).  From ``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "train.backend_start" not in spans:
        return None
    return spans["train.backend_start"]["total_s"]
