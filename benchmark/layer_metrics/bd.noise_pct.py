"""``bd.noise_ms`` as a share of the traced steps' device time: the scope
``bd_noise``'s term of the sum to 100 (with ``moe.time_share_pct`` and the
shared ``step.*_pct``).  None where the trace has no such scope."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("bd_noise",))
