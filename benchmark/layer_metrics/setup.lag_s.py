"""Seconds of set-up in which the worker's Python could not run at all:
the program's ``host.lag`` spans (the lag meter runs from before
``jax.backend_init``) between the process start of ``run.py`` and
``window_start``, summed.  A runtime start or a compile that holds the
interpreter's lock is ONE lag as long as the call; one that releases it
is none (``benchmark/lost_time.py::setup_lag`` says which spans the lags
lie in).  Nothing from a program that keeps no ``clock`` (it has no lag
meter), or where the name lost intervals from before the window, as
``setup.unattributed_s``."""

from benchmark import lost_time


def read(run):
    got = lost_time.setup_lag(run)
    return None if got is None else got["total_s"]
