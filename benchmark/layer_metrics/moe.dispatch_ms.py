"""What of the expert layer is not a matmul, in device milliseconds a
step: the scopes ``moe_route`` (norm, router, softmax, top-k, losses),
``moe_dispatch`` (sort, group sizes, gather) and ``moe_combine`` (gather
back, weighted sum, residual), all phases."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(
        d, ("moe_route", "moe_dispatch", "moe_combine")) or None
