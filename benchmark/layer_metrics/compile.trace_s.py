"""Seconds the worker spent TRACING: the program's ``jax.trace`` spans
(``ray_tpu.util.tracing.watch_process``: JAX's ``jaxpr_trace_duration`` of
every function traced on the way to a program, an inner ``jit``'s trace
inside its caller's counted once), summed over every program of the run —
the state's, the check's, the step's, each eager op's.  From
``Result.metrics["_spans"]``; a program without these spans reads nothing."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.trace" not in spans:
        return None
    return spans["jax.trace"]["total_s"]
