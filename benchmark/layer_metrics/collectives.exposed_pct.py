"""Time in collective operations on a chip's op stream — where no
compute runs beside them — as a share of the traced steps' device time;
the chip where it is largest.  No list of cells: 0.0 on one chip, whose
steps hold no collective, so a later mesh cell reports without an edit."""


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    return 100.0 * max(d["collective_s"] / sum(d["step_s"])
                       for d in trace["devices"])
