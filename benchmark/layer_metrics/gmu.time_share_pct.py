"""Device self time under the scope ``gmu`` — a gated memory unit whole:
its norm, the gate's projection and SiLU, the product with the memory an
earlier layer's scan left, the output projection and the add —, all phases,
as a share of the traced steps' device time.  None where the trace has no
such scope."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("gmu",))
