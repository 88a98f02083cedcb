"""Device milliseconds a step under the scope ``dsa_index``, all phases,
every layer — the indexer: its three projections of the detached input, the
key's LayerNorm, the rotation, and every index-score product (16 heads
against one key over all causal pairs, the ReLU, the weighted sum), forward,
rematerialised and the gradient's.  None where the trace has nothing under
the scope (a model without an indexer, a program from before the scope, an
untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("dsa_index",)) or None
