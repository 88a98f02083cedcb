"""The least time the chip could take for the attention over the SELECTED
pairs a step needs (the configuration's FLOP module, ``flops.of(conf)``:
``flash_step_flops`` — ``min(t + 1, topk)`` keys a query, counted from
shapes whatever implements it — over the bf16 peak or ``flash_step_bytes``
over the HBM peak, whichever is larger; ``bound(run)`` says which) over the
device time of the kernels ``dsa.attend_ms`` reads.  Kernels that compute
every causal pair and mask cannot pass ``selected / causal`` of what they
would read dense (23.4 % at 16384 under 2048); one that skips dead tiles
gains; one that leaves selected pairs out reads over 100.  None where
``dsa.attend_ms`` is, or the module counts no selection."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "selected_pairs"):
        return None
    return flops.roofline_seconds(
        count.flash_step_flops(run["conf"], job["rows"], job["seq"]),
        count.flash_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d, least = trace_scopes.device(run), _least(run)
    attend_s = d and sum(t for k, t in d["kernels"].items()
                         if k.startswith("flash_") and "_dsa" in k)
    if not attend_s or least is None:
        return None
    return 100.0 * least["seconds"] / attend_s
