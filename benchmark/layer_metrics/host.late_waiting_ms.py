"""The remainder of ``host.late_ms``: late intervals' milliseconds that
neither a stop of the process (``host.late_stopped_ms``) nor the loop
thread's own CPU time (``host.late_running_ms``) accounts for — the thread
was off the CPU of its own accord: in the fetch, waiting for the DEVICE or
for the runtime under it (``benchmark/lost_time.py``).  Nothing from a
program that keeps no ``clock``."""

from benchmark import lost_time


def read(run):
    got = lost_time.totals(run)
    return None if got is None else got["waiting_ms"]
