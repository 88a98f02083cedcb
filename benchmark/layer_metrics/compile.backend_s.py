"""Seconds the worker spent handing modules to the backend: the program's
``jax.compile`` spans (JAX's ``backend_compile_duration``: the cache key's
hash, then a load from the persistent cache — ``compile.cache_load_s`` is
that part — or a compile and the write), summed over every program of the
run.  With ``compile.trace_s`` and ``compile.lower_s``, JAX's share of
``setup_s``.  From ``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.compile" not in spans:
        return None
    return spans["jax.compile"]["total_s"]
