"""Seconds the worker spent reading executables back from the persistent
compile cache (file read, decompression, deserialisation onto the chip):
the program's ``jax.cache_load`` spans, each a child of the ``jax.compile``
it fell in.  0.0 in a run that handed programs to the backend and loaded
none (a cold run); nothing from a program without the spans.  From
``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.compile" not in spans:
        return None
    return spans["jax.cache_load"]["total_s"] \
        if "jax.cache_load" in spans else 0.0
