"""Device self time under the scope ``attention`` (the flash kernels and
the repeats, transposes and log-sum-exp passes round them; all phases) as
a share of the traced steps' device time."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("attention",))
