"""Device milliseconds a step under the scope ``s6_scan`` — the selective
scan's recurrence with ``D x``, whatever implements it, and what XLA does
round it (a kernel's changes of layout) —, all phases, all Mamba-1 layers.
None where the trace has nothing there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("s6_scan",)) or None
