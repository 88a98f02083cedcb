"""Device self time under the n-stream residual's two scopes (``hc_map``:
the streams normed as one vector, the maps' projection, sigmoids, Sinkhorn;
``hc_mix``: a block's input read off the streams, its output written back
into them; all phases) as a share of the traced steps' device time: with
the shared ``step.*_pct`` shares, ``moe.time_share_pct`` and ``mtp.in_pct``
it makes 100.  None where the trace has no such scope (a model of one
stream, or a program from before the scopes)."""

from benchmark import trace_scopes

HC_SCOPES = ("hc_map", "hc_mix")


def read(run):
    return trace_scopes.step_share_pct(run, HC_SCOPES)
