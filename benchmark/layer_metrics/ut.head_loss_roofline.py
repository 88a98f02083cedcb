"""The least time the chip could take for the head products a step's exits
NEED (the configuration's FLOP module, ``flops.of(conf)``:
``head_step_flops`` — a forward product and its two backward ones an exit,
over the bf16 peak) over the device time under ``lm_head`` and ``loss``
(``ut.head_loss_ms``): how far the exits stand from their products' bound.
The float32 softmaxes and the logits made again for the backward pass are
in the time and not in the count, so it reads under 100 unless the count is
wrong.  None where ``ut.head_loss_ms`` is."""

from benchmark import flops, trace_scopes


def read(run):
    d, count = trace_scopes.device(run), flops.of(run["conf"])
    if d is None or not hasattr(count, "head_step_flops"):
        return None
    seconds = trace_scopes.scope_seconds(d, ("lm_head", "loss"))
    if not seconds:
        return None
    job = run["job"]
    needed = count.head_step_flops(
        run["conf"], job["rows"] * job["seq"]) / run["chips"]
    return 100.0 * needed / run["peak"]["bf16_flops_per_s"] / seconds
