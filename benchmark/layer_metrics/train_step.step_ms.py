"""Median device time of one execution of the step program; on a mesh,
the slowest chip."""

import statistics


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return 1e3 * max(statistics.median(d["step_s"])
                     for d in trace["devices"])
