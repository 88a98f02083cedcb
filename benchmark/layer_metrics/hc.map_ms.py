"""The residual's maps of a step in device milliseconds: the scope
``hc_map`` (every block of every layer: the streams normed as one vector,
one projection to 2 n + n^2 numbers a token, sigmoids, exp and the
Sinkhorn rounds), all phases.  Defined by scope, so it reads the same
whether XLA or a kernel runs there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("hc_map",)) or None
