"""Device milliseconds a step under the scope ``attn_diff``: what
differential attention adds to the softmax mixer between the flash kernels
and the output projection — lambda, the pairs' subtraction ``a1 - lambda
a2``, the RMSNorm over a pair's doubled head, the scale —, all phases, all
attention layers.  None where the trace has nothing there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("attn_diff",)) or None
