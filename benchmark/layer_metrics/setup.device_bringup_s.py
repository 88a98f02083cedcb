"""Seconds the worker spent opening the chips it was granted, before the
loop: the program's ``device.bring_up`` span (``ray_tpu/train/backend.py::
bring_up``: ``import jax``, then ``jax.local_devices()``, the call that
starts the TPU runtime, then the check that the devices are the grant).
``spawn.worker_ready_s`` holds it too.  From ``Result.metrics["_spans"]``;
a program that leaves the bring-up to the loop reads nothing."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "device.bring_up" not in spans:
        return None
    return spans["device.bring_up"]["total_s"]
