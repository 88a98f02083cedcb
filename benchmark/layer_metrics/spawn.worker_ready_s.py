"""``fit()`` called in the driver -> first line of the loop in the worker
(two processes of one machine, wall clock)."""


def read(run):
    return run["worker"]["loop_start"] - run["fit_called"]
