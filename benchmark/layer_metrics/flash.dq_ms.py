"""Device milliseconds a step in the Mosaic kernel ``flash_dq`` (the
backward pass's gradient to the queries), all layers; on a mesh, the chip
whose steps took longest.  None where the trace names no such kernel."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "flash_dq")
