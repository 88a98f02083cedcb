"""The (q, k) pairs the windowed attention layers COMPUTE over the pairs
their window leaves, as the step's metrics report it
(``attn_window_executed_share`` of ``loss_fn``: the flash schedule's live
sub-tiles, from shapes, the largest over the windowed layers): the largest
over the steps of the window, as the reference module has the loop keep it.
1.0 would be no masked pair computed; what lies above it is the sub-tiles
that straddle the diagonal or the window's far edge.  None where the
configuration's reference names no such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "attn_window_executed_share")
