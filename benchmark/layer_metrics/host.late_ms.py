"""Milliseconds the measured window lost, by the host's clock: over the
intervals between neighbouring reports (``session.report`` spans' starts
in ``[window_start, window_start + elapsed_s]``), what each is longer than
the median one by, summed — as the sum of its three parts,
``host.late_stopped_ms`` + ``host.late_running_ms`` +
``host.late_waiting_ms``, which so make it to the float
(``benchmark/lost_time.py``).  A steady run reads under a millisecond a
step; a run that lost 0.9 s to a stall reads about 900.  Nothing from a
program whose ``session.report`` keeps no ``clock``, or under 3
intervals."""

from benchmark import lost_time


def read(run):
    got = lost_time.totals(run)
    return None if got is None else got["late_ms"]
