"""Device milliseconds a step under the scope ``dsa_loss``, all phases,
every layer — the indexer's loss: the heads' attention probabilities remade
over the selection and averaged, the KL to the softmax of the index scores
there, and its gradient to the scores.  None where the trace has nothing
under the scope (a model without an indexer, a program from before the
scope, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("dsa_loss",)) or None
