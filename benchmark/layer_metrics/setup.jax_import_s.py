"""Seconds of the bring-up that are ``import jax``: the program's
``jax.import`` span, a child of ``device.bring_up`` (what is left of the
parent is ``jax.local_devices()``, the TPU runtime's start).  From
``Result.metrics["_spans"]``; a program without the span reads nothing."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.import" not in spans:
        return None
    return spans["jax.import"]["total_s"]
