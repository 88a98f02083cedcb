"""Device self time under a Mamba-1 mixer's four scopes (``s6_in``,
``s6_conv``, ``s6_scan``, ``s6_out``; all phases) as a share of the traced
steps' device time: with ``gmu.time_share_pct``, ``diffattn.combine_pct``
and the shared ``step.*_pct`` shares it makes 100.  None where the trace has
no such scope (a model without Mamba-1 layers, a program from before the
scopes, an untraced run)."""

from benchmark import trace_scopes

SCOPES = ("s6_in", "s6_conv", "s6_scan", "s6_out")


def read(run):
    return trace_scopes.step_share_pct(run, SCOPES)
