"""Device milliseconds a step in the Mosaic kernel ``flash_fwd`` (attention
forward; a rematerialised run of it counts too), all layers; on a mesh, the
chip whose steps took longest.  None where the trace names no such kernel."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "flash_fwd")
