"""What of a delta-rule mixer lies between its input projection and its
rule, in device milliseconds a step: the scope ``gdn_conv`` (the causal
depthwise convolution over q, k, v with its SiLU, the L2 norms of q and k,
``beta`` and the log-decay), all phases.  Elementwise work: the bytes of
(tokens, 11520) set its pace."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("gdn_conv",)) or None
