"""The (q, k) pairs the block-rule attention COMPUTES over the pairs the
rule needs (``seq (seq + B)`` a head), as the step's metrics report it
(``attn_bd_executed_share`` of ``loss_fn``: the ``flash_*_bd`` schedule's
live sub-tiles, from shapes — both streams' on the clean keys and the noised
stream's diagonal squares on its own): the largest over the steps of the
window, as the reference module has the loop keep it.  1.0 would be no
masked pair computed; what lies above it is the sub-tiles on the diagonal.
A schedule that ran the clean rows against the noised keys, or the noised
stream's off-diagonal blocks, would read over 2.  None where the
configuration's reference names no such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "attn_bd_executed_share")
