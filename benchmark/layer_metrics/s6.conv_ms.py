"""What of a Mamba-1 mixer lies between its input projection and its scan,
in device milliseconds a step: the scope ``s6_conv`` (the causal depthwise
convolution with its SiLU, the projection to dt's rank, B and C, dt's
projection and softplus), all phases.  None where the trace has nothing
there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("s6_conv",)) or None
