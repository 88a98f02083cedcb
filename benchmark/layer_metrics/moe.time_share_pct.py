"""Device self time under the expert layer's four scopes (``moe_route``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``; all phases) as a share
of the traced steps' device time.  None where the trace has no such scope
(a dense model, or a program from before the scopes)."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, trace_scopes.MOE_SCOPES)
