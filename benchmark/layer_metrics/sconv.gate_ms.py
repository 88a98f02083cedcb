"""The gated short convolutions of a step in device milliseconds: the
scope ``sconv_gate`` (``C * conv(B * x)`` of every conv layer: both
elementwise gates and the depthwise causal convolution between them), all
phases.  Defined by scope, so it reads the same whether XLA or a kernel
runs there.  Elementwise work: the bytes of (tokens, 3 x hidden) in and
(tokens, hidden) out set its pace."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("sconv_gate",)) or None
