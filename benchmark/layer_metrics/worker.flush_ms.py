"""Milliseconds of the measured window under the worker's periodic thread
(``_private/worker_main.py::decref_flusher``, every 0.25 s, under the
interpreter's lock in the process that owns the chips): the program's
process-wide ``worker.flush`` spans — an iteration that took over 1 ms —
that START in the window, summed.  0.0 where none did; nothing from a
program that keeps no ``clock`` (it has no such span)."""

from benchmark import lost_time


def read(run):
    got = lost_time.flush_s(run)
    return None if got is None else 1e3 * got
