"""The state-space scans of a step in device milliseconds: the scope
``ssm_scan`` (the chunked scan of every Mamba layer, ``D x`` included),
all phases.  Defined by scope, so it reads the same whether XLA or a
kernel runs there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("ssm_scan",)) or None
