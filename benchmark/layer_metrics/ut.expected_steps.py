"""The passes a token takes before it exits, in expectation under the exit
distribution the gate gives (``ut_expected_steps`` of ``loss_fn``: the mean
over positions of ``sum_t t p_t``, between 1 and the passes run): the
largest over the steps of the window, as the reference module has the loop
keep it.  A step's work does not follow it: every pass is run for every
token.  None where the configuration's reference names no such step
metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "ut_expected_steps")
