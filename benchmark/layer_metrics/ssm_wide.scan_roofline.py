"""The least time the chip could take for the state-space scans a step
needs (the configuration's FLOP module, ``flops.of(conf)``:
``ssd_step_flops`` over the bf16 peak or ``ssd_step_bytes`` over the HBM
peak, whichever is larger — ``bound(run)`` says which) over the device time
of the scope ``ssm_scan``, all phases, in the cell whose mixer has 128 heads
x 64 in 8 groups.  ONE QUANTITY UNDER TWO NAMES: this is what
``ssm.groups_scan_roofline`` reads, through the same ``flops.of(conf)``,
under a name of its own because that entry's list of cells is held by
equality (``benchmark/tests/test_nemotron_h.py``); a ``benchmark`` PR folds
the two (``PERF.md`` section 7, From PR 64 (a)).

The structure's ceiling is that reader's: the scope runs the forward pass
twice (the layer checkpoint keeps nothing of the scan), and of the counted
bytes a layer the forward is 2 x + B + C + dt of 5 x + 3 (B + C + dt), so
(5 x + 3 r) / (7 x + 4 r) — 71.9 % at 128 heads x 64 with 8 groups of 128 —
is the most it can read where the bytes bound it, 75 % where the operations
do.  None where the module counts no scan or the trace has nothing under
the scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "ssd_step_flops"):
        return None
    return flops.roofline_seconds(
        count.ssd_step_flops(run["conf"], job["rows"], job["seq"]),
        count.ssd_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("ssm_scan",))
    least = _least(run)
    if not scan_s or least is None:
        return None
    return 100.0 * least["seconds"] / scan_s
