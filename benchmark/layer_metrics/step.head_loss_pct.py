"""Device self time under the scopes ``embed``, ``lm_head`` and ``loss``
(what does not grow with depth; all phases) as a share of the traced
steps' device time."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("embed", "lm_head", "loss"))
