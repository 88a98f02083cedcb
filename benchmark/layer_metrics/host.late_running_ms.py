"""Of ``host.late_ms`` less ``host.late_stopped_ms``, the milliseconds the
loop thread was ON the CPU beyond the median interval's: its
``time.thread_time()`` between the two reports of each late interval, from
``clock`` of ``Result.metrics["_spans"]["session.report"]``
(``benchmark/lost_time.py``) — the host's Python was busy.  Nothing from a
program that keeps no ``clock``."""

from benchmark import lost_time


def read(run):
    got = lost_time.totals(run)
    return None if got is None else got["running_ms"]
