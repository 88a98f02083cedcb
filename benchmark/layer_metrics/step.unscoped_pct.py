"""Device self time of the ops that carry neither a step scope nor the
layer scan's ``while`` as a share of the traced steps' device time: with
the other ``step.*_pct`` (and a model's own share, such as
``moe.time_share_pct``) it makes 100."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 100.0 * d["unscoped_s"] * d["steps"] / sum(d["step_s"])
