"""Device self time under the scope ``optimizer`` as a share of the traced
steps' device time."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("optimizer",))
