"""Device time in the Mosaic kernels named ``flash_*`` (forward, dKV, dQ) as a
share of the traced steps' device time; on a mesh, the chip where it is
largest."""


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return 100.0 * max(d["flash_s"] / sum(d["step_s"])
                       for d in trace["devices"])
