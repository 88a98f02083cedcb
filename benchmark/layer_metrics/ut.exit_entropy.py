"""The entropy of a token's exit distribution in nats, the mean over a
step's positions (``ut_exit_entropy`` of ``loss_fn``; between 0, a gate
that always exits at one pass, and the log of the passes run, the uniform
exit): the largest over the steps of the window, as the reference module has
the loop keep it.  None where the configuration's reference names no such
step metric."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "ut_exit_entropy")
