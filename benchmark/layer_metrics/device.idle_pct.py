"""1 - (union of the op intervals) / window over the traced steps, on the
chip that idles most.  idle + busy = 100 exactly."""


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return 100.0 * max(d["idle_s"] / d["window_s"] for d in trace["devices"])
