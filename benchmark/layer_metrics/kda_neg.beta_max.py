"""The largest write strength ``beta`` any KDA layer applied in the window,
as the step's metrics report it (``kda_beta_max`` of ``loss_fn``, which a
model with ``kda_neg_eigval`` reports; the reference module has the loop
keep the window's maximum): over 1 says the negative-eigenvalue path ran
(``beta = 2 sigmoid``, under 2), at or under 1 that the factor was lost.
None where the configuration's reference names no such step metric or the
program reports none."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "kda_beta_max")
