"""Device milliseconds a step in the kernels that compute the attention
under the block rule, forward and backward, every layer: the Mosaic calls
named ``flash_*_bd`` (the flash kernels over two streams of one sequence,
``[noised ; clean]``).  None where the trace names no such kernel (a model
of another objective, a program that attends another way: the reader then
needs that way's name)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * sum(t for k, t in d["kernels"].items()
                     if k.startswith("flash_") and "_bd" in k) or None
