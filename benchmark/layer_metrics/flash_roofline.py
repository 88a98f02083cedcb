"""The least time a chip could take for the attention a step needs (the
FLOP module the configuration names, ``benchmark/flops.py::of``: causal
FLOPs of its attention layers over the bf16 peak or bytes over the HBM
peak, whichever is larger — ``bound(run)`` says which) over the device time
of the Mosaic kernels named ``flash_*``; each chip of a mesh has 1/chips of
the work, and the slowest chip is read."""

from benchmark import flops


def _least(run):
    conf, job = run["conf"], run["job"]
    count = flops.of(conf)
    return flops.roofline_seconds(
        count.flash_step_flops(conf, job["rows"], job["seq"]) / run["chips"],
        count.flash_step_bytes(conf, job["rows"], job["seq"]) / run["chips"],
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    d = max(trace["devices"], key=lambda d: d["flash_s"])
    if not d["flash_s"]:
        return None
    return 100.0 * _least(run)["seconds"] * d["steps"] / d["flash_s"]
