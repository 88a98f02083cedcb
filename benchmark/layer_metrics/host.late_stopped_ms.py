"""Of ``host.late_ms``, the milliseconds in which NO Python thread of the
worker could run: what the program's process-wide spans ``host.lag`` (the
lag meter woke over 20 ms late: the process was descheduled or stopped, or
a thread held the interpreter through a call that does not release it) and
``gc.pause`` cover of the late intervals — their union clipped to each
interval, no more than the interval is late by (``benchmark/lost_time.py``).
Nothing from a program whose ``session.report`` keeps no ``clock``."""

from benchmark import lost_time


def read(run):
    got = lost_time.totals(run)
    return None if got is None else got["stopped_ms"]
