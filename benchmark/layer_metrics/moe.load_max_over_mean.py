"""The busiest expert's assignments over the mean (1 = perfectly even),
worst layer, as the step's metrics report it (``moe_load_max_over_mean``
of ``loss_fn``): the largest over the steps of the window, as the
reference module has the loop keep it.  None where the configuration's
reference names no such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "moe_load_max_over_mean")
