"""The least time the chip could take for the delta rules a step needs
(the configuration's FLOP module, ``flops.of(conf)``: ``gdn_step_flops``
over the bf16 peak or ``gdn_step_bytes`` over the HBM peak, whichever is
larger — ``bound(run)`` says which) over the device time of the scope
``gdn_scan``, all phases.  Defined by scope, so it reads the same whether
XLA or a Pallas kernel runs there.

Counted is the RECURRENCE (6 x key size x value size operations a token
and head forward, twice that backward) and the fewest reads and writes of
q, k, v, the two gates, the output and their gradients; what a chunked
form does beyond that is its overhead.  The structure's ceiling: the scope
runs the forward pass twice (the layer checkpoint keeps nothing of the
rule), and the forward is 142.5 of the 380.4 MB a layer that are counted,
so 380.4 / 522.9 = 72.7 % is the most this structure can read.  None where
the module counts no delta rule or the trace has nothing under the
scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "gdn_step_flops"):
        return None
    return flops.roofline_seconds(
        count.gdn_step_flops(run["conf"], job["rows"], job["seq"]),
        count.gdn_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("gdn_scan",))
    least = _least(run)
    if not scan_s or least is None:
        return None
    return 100.0 * least["seconds"] / scan_s
