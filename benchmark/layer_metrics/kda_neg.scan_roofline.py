"""The least time the chip could take for the KDA rules a step needs (the
configuration's FLOP module, ``flops.of(conf)``: ``kda_step_flops`` over
the bf16 peak or ``kda_step_bytes`` over the HBM peak, whichever is larger
— ``bound(run)`` says which) over the device time of the scope
``kda_scan``, all phases, in the cell whose write strength reaches 2.
Defined by SCOPE, so it reads on one scale whether XLA or a Pallas kernel
runs there, and on ``kda.scan_roofline``'s scale (the count is
``flops_kimi_linear.py``'s, the name its own because that entry's list is
held by equality).

Counted is the RECURRENCE (6 x 128 x 128 operations a token and head
forward, twice that backward) and the fewest reads and writes of q, k, v,
the ``(tokens, heads, 128)`` float32 log-decays, beta, the output and their
gradients; what a chunked form does beyond that is its overhead.  The
structure's ceiling: the scope runs the forward pass twice (the layer
checkpoint keeps nothing of the rule), and the forward is 403.7 of the
1143.9 MB a layer that are counted at 4096 tokens of 64 heads, so 1143.9 /
1547.6 = 73.9 % is the most this structure can read
(``flops_solar_open2.kda_scan_ceiling_pct``).  None where the module counts
no such rule or the trace has nothing under the scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "kda_step_flops"):
        return None
    return flops.roofline_seconds(
        count.kda_step_flops(run["conf"], job["rows"], job["seq"]),
        count.kda_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("kda_scan",))
    least = _least(run)
    if not scan_s or least is None:
        return None
    return 100.0 * least["seconds"] / scan_s
