"""The least time the chip could take for the grouped products a step
needs (the FLOP module the configuration names, ``benchmark/flops.py::of``:
its ``experts_step_flops`` over the bf16 peak or its ``experts_step_bytes``
over the HBM peak, whichever is larger — ``bound(run)`` says which) over the
device time of the scope ``moe_experts``, all phases.  The module counts what
THIS chip's experts compute: of one chip's share, the rows that land on the
experts held and never the absent experts' work.  Defined by scope, so it
reads the same whether kernels or ``ragged_dot`` run there.  The scope runs
the forward twice under remat and holds SwiGLU, so the structure's ceiling
is 75 % before any padding."""

from benchmark import flops, trace_scopes


def _least(run):
    conf, job = run["conf"], run["job"]
    count = flops.of(conf)
    return flops.roofline_seconds(
        count.experts_step_flops(conf, job["rows"], job["seq"]),
        count.experts_step_bytes(conf, job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    d = trace_scopes.device(run)
    experts_s = d and trace_scopes.scope_seconds(d, ("moe_experts",))
    if not experts_s:
        return None
    return 100.0 * _least(run)["seconds"] / experts_s
