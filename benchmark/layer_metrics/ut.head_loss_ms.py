"""Device milliseconds a step under the scopes ``lm_head`` and ``loss`` of
a model that reads its head after EVERY pass of a looped stack: all the
exits' last norms, head products, float32 softmaxes and their backward
passes, and the logits each pass makes again for its backward pass (phase
``remat``).  None where the run has no trace, or the configuration's FLOP
module counts no exits (``head_step_flops``): a model of one head has
``step.head_loss_pct``."""

from benchmark import flops, trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None or not hasattr(flops.of(run["conf"]), "head_step_flops"):
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("lm_head", "loss")) or None
