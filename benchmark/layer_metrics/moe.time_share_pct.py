"""Device self time under the expert layer's four scopes (``moe_route``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``; all phases) as a share
of the traced steps' device time.  The entry carries no list of cells, so
that a later expert model reports here without an edit: a traced step of
which nothing ran under a ``moe_*`` scope reads 0.0 in a dense model, as
``step.ffn_pct`` does where an expert layer stands in the FFN's place, and
the cell's shares still make 100.  None where no step was traced, and None
where the configuration's FLOP module counts an expert layer
(``flops.counts_experts``) and the step ran nothing under its scopes: the
name stacks are lost then, and a null in a cell's line is refused, where a
0 would show in ``step.unscoped_pct`` alone."""

from benchmark import flops, trace_scopes


def read(run):
    if trace_scopes.device(run) is None:
        return None
    share = trace_scopes.step_share_pct(run, trace_scopes.MOE_SCOPES)
    if share is None and flops.counts_experts(run["conf"]):
        return None
    return share or 0.0
