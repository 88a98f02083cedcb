"""Device self time under the expert layer's four scopes (``moe_route``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``; all phases) as a share
of the traced steps' device time.  None where the trace has no such scope
(a program from before the scopes)."""

from benchmark import trace_scopes


def read(run):
    trace = run["worker"]["trace"]
    if not trace:
        return None
    d = trace["devices"][0]
    moe = trace_scopes.scope_seconds(d, trace_scopes.MOE_SCOPES) \
        if "scopes" in d else 0.0
    if not moe:
        return None
    return 100.0 * moe * d["steps"] / sum(d["step_s"])
