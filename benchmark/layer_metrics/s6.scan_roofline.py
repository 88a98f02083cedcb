"""The least time the chip could take for the selective scans a step needs
(the configuration's FLOP module, ``flops.of(conf)``: ``selscan_step_flops``
over the bf16 peak or ``selscan_step_bytes`` — x, dt, B, C read and m
written once a pass, forward and backward — over the HBM peak, whichever is
larger; ``bound(run)`` says which) over the device time of the scope
``s6_scan``, all phases.  Defined by scope, so it reads the same whether XLA
or a Pallas kernel runs there.  It reads LOW where the vector unit binds:
the recurrence is 16 elementwise state updates a channel and token, which
neither peak measures; a forward run again under the layer checkpoint
lowers it further.  None where the scope has no time or the module counts
no such scan."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "selscan_step_bytes"):
        return None
    return flops.roofline_seconds(
        count.selscan_step_flops(run["conf"], job["rows"], job["seq"]),
        count.selscan_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d, least = trace_scopes.device(run), _least(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("s6_scan",))
    if not scan_s or least is None:
        return None
    return 100.0 * least["seconds"] / scan_s
