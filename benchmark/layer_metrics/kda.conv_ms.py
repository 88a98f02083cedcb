"""What of a KDA mixer lies between its input projection and its rule, in
device milliseconds a step: the scope ``kda_conv`` (the causal depthwise
convolution over q, k, v with its SiLU, the L2 norms of q and k, ``beta``,
and the log-decay a key channel: its up-projection, softplus and
``-exp(A_log)``), all phases."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("kda_conv",)) or None
