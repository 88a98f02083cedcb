"""Device milliseconds a step in the Mosaic kernel ``flash_dkv`` (the
backward pass's gradients to keys and values), all layers; on a mesh, the
chip whose steps took longest.  None where the trace names no such kernel."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "flash_dkv")
