"""Device milliseconds a step in the kernels that compute the attention over
the selected pairs, forward and backward, every layer: the Mosaic calls
named ``flash_*_dsa`` (the flash kernels under a mask that is data).  None
where the trace names no such kernel (a model without an indexer, a program
that attends another way: the reader then needs that way's name)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * sum(t for k, t in d["kernels"].items()
                     if k.startswith("flash_") and "_dsa" in k) or None
