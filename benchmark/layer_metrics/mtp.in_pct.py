"""Device self time under the scope ``mtp_in`` (a predicted-ahead module's
input: the norms of the model's last stream and of the next token's
embedding, the two side by side, the projection back to the model's width;
all phases) as a share of the traced steps' device time.  The module's
block and head run under the scopes every layer and head use.  None where
the trace has no such scope."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("mtp_in",))
