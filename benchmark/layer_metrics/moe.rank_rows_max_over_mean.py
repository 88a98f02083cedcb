"""The most live rows on an ``ep`` rank over the ranks' mean (1 = every
chip has as many rows to compute; the others wait for the fullest in the
exchange's scatter-sum), worst layer, as the step's metrics report it
(``moe_rank_rows_max_over_mean`` of ``loss_fn``): the largest over the
steps of the window, as the reference module has the loop keep it.  None
where the configuration's reference names no such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "moe_rank_rows_max_over_mean")
