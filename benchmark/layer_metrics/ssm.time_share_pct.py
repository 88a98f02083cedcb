"""Device self time under a Mamba mixer's four scopes (``ssm_in``,
``ssm_conv``, ``ssm_scan``, ``ssm_out``; all phases) as a share of the
traced steps' device time: with the shared ``step.*_pct`` shares it makes
100.  None where the trace has no such scope (a model without Mamba
layers, or a program from before the scopes)."""

from benchmark import trace_scopes

SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")


def read(run):
    return trace_scopes.step_share_pct(run, SSM_SCOPES)
