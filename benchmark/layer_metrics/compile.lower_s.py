"""Seconds the worker spent LOWERING jaxprs to MLIR modules, the bodies of
Pallas kernels to Mosaic included: the program's ``jax.lower`` spans
(JAX's ``jaxpr_to_mlir_module_duration``), summed over every program of
the run.  From ``Result.metrics["_spans"]``."""


def read(run):
    spans = run["worker"].get("_spans") or {}
    if "jax.lower" not in spans:
        return None
    return spans["jax.lower"]["total_s"]
