"""Device self time under the scope ``moe_latent`` (the pair of projections
round the routed experts of a model whose experts work in a LATENT: the
normed tokens ``d -> l`` before the dispatch, each token's summed parts ``l
-> d`` after the combine, and their gradients; all phases, every expert
layer, a predicted-ahead module's among them) as a share of the traced
steps' device time.  With ``step.*_pct``, ``moe.time_share_pct`` (the four
older ``moe_*`` scopes) and ``ssm.time_share_pct`` the cell's shares make
100.  None where the trace has no such scope (a program from before it, an
untraced run)."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("moe_latent",))
