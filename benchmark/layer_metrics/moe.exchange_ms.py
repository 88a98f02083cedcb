"""Device milliseconds a step under the scope ``moe_exchange`` on one chip,
all phases: the collectives that bring an ``ep`` rank the tokens its
experts need and return their parts to the ranks that own the tokens
(forward, the rematerialised forward and backward), with the copies round
them — and whatever a rank WAITS there for a fuller rank, which
``moe.rank_rows_max_over_mean`` reads beside it.  The chip every reader of
scopes reads (``trace_scopes.device``).  None where the trace has no such
scope: a cell on one chip, a program from before the exchange."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    seconds = d and trace_scopes.scope_seconds(d, ("moe_exchange",))
    return 1e3 * seconds if seconds else None
