"""The busiest expert's assignments over the mean (1 = perfectly even),
worst layer, as the step's metrics report it (``moe_load_max_over_mean``
of ``loss_fn``): the largest over the steps of the window.  None where the
loop reported no such counter."""


def read(run):
    values = run["worker"]["window"].get("moe_load_max_over_mean")
    return max(values) if values else None
