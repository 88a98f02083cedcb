"""Device self time under a delta-rule mixer's four scopes (``gdn_in``,
``gdn_conv``, ``gdn_scan``, ``gdn_out``; all phases) as a share of the
traced steps' device time: with the shared ``step.*_pct`` shares it makes
100.  None where the trace has no such scope (a model without linear
layers, or a program from before the scopes)."""

from benchmark import trace_scopes

GDN_SCOPES = ("gdn_in", "gdn_conv", "gdn_scan", "gdn_out")


def read(run):
    return trace_scopes.step_share_pct(run, GDN_SCOPES)
