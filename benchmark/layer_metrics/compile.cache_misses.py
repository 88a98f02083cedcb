"""``/jax/compilation_cache/cache_misses`` events in the worker, whole
process.  0 on every run after a cell's first in a checkout."""


def read(run):
    return run["worker"]["compile"]["cache_misses"]
