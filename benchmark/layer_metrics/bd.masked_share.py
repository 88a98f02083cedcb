"""The share of a step's positions the block-diffusion noise masked, as the
step's metrics report it (``bd_masked_share`` of ``loss_fn``: the mean of
``m``; in expectation ``p = (1 - eps) t + eps`` of the step's one ``t ~ U(0,
1)`` a sequence): the largest over the steps of the window, as the reference
module has the loop keep it.  A step's work does not depend on it (every
position is computed; the loss alone follows the mask).  None where the
configuration's reference names no such step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "bd_masked_share")
