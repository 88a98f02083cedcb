"""The least time the chip could take for the grouped products a step
needs (``benchmark/flops_moe.py``: their FLOPs over the bf16 peak or their
bytes over the HBM peak, whichever is larger — ``bound(run)`` says which)
over the device time of the scope ``moe_experts``, all phases.  Defined by
scope, so it reads the same whether kernels or ``ragged_dot`` run there.
The scope runs the forward twice under remat and holds SwiGLU, so the
structure's ceiling is 75 % before any padding."""

from benchmark import flops, flops_moe, trace_scopes


def _least(run):
    job = run["job"]
    return flops.roofline_seconds(
        flops_moe.experts_step_flops(run["conf"], job["rows"], job["seq"]),
        flops_moe.experts_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    d = trace_scopes.device(run)
    experts_s = d and trace_scopes.scope_seconds(d, ("moe_experts",))
    if not experts_s:
        return None
    return 100.0 * _least(run)["seconds"] / experts_s
