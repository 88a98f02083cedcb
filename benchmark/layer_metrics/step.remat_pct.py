"""Device self time of the rematerialised forward (the phase ``remat``,
over every scope) as a share of the traced steps' device time.  A phase
cuts across the scopes: this share is inside the ``step.*_pct`` of the
scopes, not beside them."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, None, ("remat",))
