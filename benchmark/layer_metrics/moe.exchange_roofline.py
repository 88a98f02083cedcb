"""The least time one chip's interconnect could take for the expert
layers' exchange a step needs — ``exchange_step_bytes`` of the FLOP module
the configuration names (``benchmark/flops.py::of``): from shapes alone,
whatever form the program gives the exchange, the bytes of the tokens that
other ranks own and this chip's experts are expected to need, in and back,
forward and backward — over ``peaks.json``'s ``ici_bytes_per_s``, as a share
of the device time of the scope ``moe_exchange``, all phases
(``moe.exchange_ms``).  One direction's bytes against the published rate
of a chip's links: the count cannot pass what the links carry.  The
structure's ceiling: the program's all-gather moves EVERY token of the
other ranks where ``needs_rank_share`` of them are counted, and the forward
gather runs again under the layer checkpoint.  None where the trace has no
such scope or the module no such count."""

from benchmark import flops, trace_scopes


def read(run):
    d = trace_scopes.device(run)
    seconds = d and trace_scopes.scope_seconds(d, ("moe_exchange",))
    count = flops.of(run["conf"])
    if not seconds or not hasattr(count, "exchange_step_bytes"):
        return None
    job = run["job"]
    least = count.exchange_step_bytes(
        run["conf"], job["rows"], job["seq"]) / run["peak"]["ici_bytes_per_s"]
    return 100.0 * least / seconds
