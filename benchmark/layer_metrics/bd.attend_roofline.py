"""The least time the chip could take for the attention over the pairs the
block rule NEEDS in a step (the configuration's FLOP module,
``flops.of(conf)``: ``flash_step_flops`` — ``seq (seq + B)`` pairs a head
and layer, both streams, counted from shapes whatever implements it — over
the bf16 peak, or ``flash_step_bytes`` over the HBM peak, whichever is
larger; ``bound(run)`` says which) over the device time of the kernels
``bd.attend_ms`` reads.  Kernels that walked the causal square of the 2 seq
rows under a mask could not pass half of what they would read dense; ones
that leave needed pairs out read over 100.  None where ``bd.attend_ms`` is,
or the module counts no such pairs."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "needed_pairs"):
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
                         if k.startswith("flash_") and "_bd" in k)
    if not attend_s or least is None:
        return None
    return 100.0 * least["seconds"] / attend_s
