"""Collective operations executed per step on one chip (an async pair
counts once), from the trace.  No list of cells: 0 on one chip."""


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return max(d["collectives_per_step"] for d in trace["devices"])
