"""Median time between one step's end on the device and the next step's
first device operation; on a mesh, the chip where that is longest."""

import statistics


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return 1e3 * max(statistics.median(d["gap_s"])
                     for d in trace["devices"])
