"""Programs the worker handed to the backend in the whole run, loaded from
the cache or compiled: the count of the program's ``jax.compile`` spans.
The state's, the check's and the step's, and every eager op on an array,
which is a program with a cache lookup of its own.  From
``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.compile" not in spans:
        return None
    return spans["jax.compile"]["count"]
