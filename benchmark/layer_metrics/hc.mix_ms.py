"""The residual's mixing of a step in device milliseconds: the scope
``hc_mix`` (every block of every layer: ``Hpre X`` read off the streams,
``Hres X + Hpost^T y`` written back; the streams summed at the end), all
phases.  Defined by scope, so it reads the same whether XLA or a kernel
runs there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("hc_mix",)) or None
