"""The least time the chip could take for the state-space scans a step
needs (``benchmark/flops_hybrid.py``: ``ssd_step_flops`` over the bf16
peak or ``ssd_step_bytes`` over the HBM peak, whichever is larger —
``bound(run)`` says which) over the device time of the scope ``ssm_scan``,
all phases.  Defined by scope, so it reads the same whether XLA or a
Pallas kernel runs there.

The structure's ceiling: the scope runs the forward pass twice (the layer
checkpoint keeps nothing of a Mamba layer), and the forward is 140.5 of
the 354.4 MB a layer that are counted, so 354.4 / 494.9 = 71.6 % is the
most this structure can read where the bytes bound it (75 % where the
operations do).  A form that writes the (chunks, heads, chunk, chunk)
matrices to memory and reads them back stands far under that."""

from benchmark import flops, flops_hybrid, trace_scopes


def _least(run):
    job = run["job"]
    return flops.roofline_seconds(
        flops_hybrid.ssd_step_flops(run["conf"], job["rows"], job["seq"]),
        flops_hybrid.ssd_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    d = trace_scopes.device(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("ssm_scan",))
    if not scan_s:
        return None
    return 100.0 * _least(run)["seconds"] / scan_s
