"""What of a Mamba mixer lies between its input projection and its scan,
in device milliseconds a step: the scope ``ssm_conv`` (the causal
depthwise convolution over x, B, C with its SiLU, and dt's softplus), all
phases.  Elementwise work: the bytes of (tokens, 4352) set its pace."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("ssm_conv",)) or None
