"""What of a Mamba mixer lies between its scan and the residual, in device
milliseconds a step: the scope ``ssm_out`` (the norm of the gated output —
over each group's own channels where the model has several —, the output
projection, the residual add), all phases.  The product's time is the
MXU's; what is over it is the norm's: elementwise work and, where a
reshape puts the groups in an axis of their own, its relayouts."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("ssm_out",)) or None
