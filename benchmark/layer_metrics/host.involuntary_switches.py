"""Involuntary context switches of the loop thread over the measured
window: ``ru_nivcsw`` of ``getrusage(RUSAGE_THREAD)`` at the window's last
report less at its first, from ``clock`` of
``Result.metrics["_spans"]["session.report"]`` — how hard the machine's
other tenants press on this thread, stall or no stall.  Nothing from a
program that keeps no ``clock``, or under 3 intervals."""

from benchmark import lost_time


def read(run):
    got = lost_time.totals(run)
    return None if got is None else got["involuntary"]
