"""What of the expert layer is not a matmul, in device milliseconds a
step: the scopes ``moe_route`` (norm, router, softmax, top-k, losses),
``moe_dispatch`` (sort, group sizes, gather) and ``moe_combine`` (gather
back, weighted sum, residual), all phases.  No list of cells, as
``moe.time_share_pct``: 0.0 in a traced step of a dense model, None where no
step was traced, and None where an expert model's step (``flops.
counts_experts``) ran nothing under them, which is a fault and no zero."""

from benchmark import flops, trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    seconds = trace_scopes.scope_seconds(
        d, ("moe_route", "moe_dispatch", "moe_combine"))
    if not seconds and flops.counts_experts(run["conf"]):
        return None
    return 1e3 * seconds
