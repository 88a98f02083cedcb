"""The (query, key) pairs the selections of a step hold over the causal
pairs, the layers' mean, as the step's metrics report it
(``dsa_selected_share`` of ``loss_fn``: the PROGRAM counts the mask it
made, so a selection that admits more or fewer keys reads another number
than ``topk`` keys a query give — 0.2344 at 16384 tokens under 2048); the
largest over the steps of the window, as the reference module has the loop
keep it.  None where the configuration's reference names no such step
metric or the program reports none."""


def read(run):
    return run["worker"]["window"].get("step_metrics", {}).get(
        "dsa_selected_share")
