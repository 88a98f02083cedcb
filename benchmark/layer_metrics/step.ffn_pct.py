"""Device self time under the scope ``ffn`` (the dense SwiGLU block; all
phases) as a share of the traced steps' device time.  The entry carries no
list of cells, so that a later model with a scope ``ffn`` reports here
without an edit: a traced step of which nothing ran under ``ffn`` (an
expert layer stands in its place and reports ``moe.time_share_pct``) reads
0.0, and the cell's shares still make 100.  None where no step was traced.
"""

from benchmark import trace_scopes


def read(run):
    if trace_scopes.device(run) is None:
        return None
    return trace_scopes.step_share_pct(run, ("ffn",)) or 0.0
