"""Device self time a step under the scope ``ut_exit`` — a looped model's
exit gate after every pass and, behind the passes, the exit distribution,
its entropy and the mixture of the passes' losses —, all phases, as a share
of the traced steps' device time: the scope's term of the sum to 100 (with
the shared ``step.*_pct``).  None where the trace has no such scope."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("ut_exit",))
