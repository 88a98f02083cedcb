"""The KDA rules of a step in device milliseconds: the scope ``kda_scan``
(the chunked rule of every KDA layer: the chunks' decayed products level
by level, their inverses, the scan over chunks, the outputs), all phases.
Defined by scope, so it reads the same whether XLA or a kernel runs
there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("kda_scan",)) or None
