"""The share of the (token, expert) assignments that name an expert THIS
chip holds, mean over the expert layers, as the step's metrics report it
(``moe_held_share`` of ``loss_fn``): the largest over the steps of the
window, as the reference module has the loop keep it.  Of one chip's share
of a layer divided over 8 it is about 1 / 8, and what the expert kernels
compute follows it.  None where the configuration's reference names no
such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "moe_held_share")
