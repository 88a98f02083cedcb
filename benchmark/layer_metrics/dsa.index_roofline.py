"""The least time the chip could take for what the scope ``dsa_index`` needs
in a step (the configuration's FLOP module, ``flops.of(conf)``:
``index_step_flops`` — the indexer's three projections, the score products
over ALL causal pairs forward and the two gradient products over the
selected pairs — over the bf16 peak or ``index_step_bytes`` over the HBM
peak, whichever is larger; ``bound(run)`` says which) over the device time
under the scope, all phases.  A rematerialised forward and products remade
in the backward pass are executed and not counted.  None where the module
counts no indexer or the trace has nothing under the scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "index_step_flops"):
        return None
    return flops.roofline_seconds(
        count.index_step_flops(run["conf"], job["rows"], job["seq"]),
        count.index_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    index_s = d and trace_scopes.scope_seconds(d, ("dsa_index",))
    least = _least(run)
    if not index_s or least is None:
        return None
    return 100.0 * least["seconds"] / index_s
