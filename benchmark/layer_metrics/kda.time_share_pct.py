"""Device self time under a KDA mixer's four scopes (``kda_in``,
``kda_conv``, ``kda_scan``, ``kda_out``; all phases) as a share of the
traced steps' device time: with the shared ``step.*_pct`` shares and
``moe.time_share_pct`` it makes 100.  None where the trace has no such
scope (a model without KDA layers, or a program from before the scopes)."""

from benchmark import trace_scopes

KDA_SCOPES = ("kda_in", "kda_conv", "kda_scan", "kda_out")


def read(run):
    return trace_scopes.step_share_pct(run, KDA_SCOPES)
