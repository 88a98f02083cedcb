"""Device milliseconds a step under the scope ``dsa_select``, all phases,
every layer — the selection: a row's top-k (its threshold and the tie's key)
and the mask made from them; the layer checkpoint keeps the two numbers a
row, so nothing is selected twice, and the mask is made again.  None where
the trace has nothing under the scope (a model without an indexer, a program
from before the scope, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("dsa_select",)) or None
