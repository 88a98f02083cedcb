"""What of the expert layer is not a matmul, in device milliseconds a
step: the scopes ``moe_route`` (norm, router, softmax, top-k, losses),
``moe_dispatch`` (sort, group sizes, gather) and ``moe_combine`` (gather
back, weighted sum, residual), all phases."""

from benchmark import trace_scopes


def read(run):
    trace = run["worker"]["trace"]
    if not trace or "scopes" not in trace["devices"][0]:
        return None
    ms = 1e3 * trace_scopes.scope_seconds(
        trace["devices"][0], ("moe_route", "moe_dispatch", "moe_combine"))
    return ms or None
