"""The least time the chip could take for the state-space scans a step
needs — the FLOP module the configuration names (``benchmark/flops.py::of``):
its ``ssd_step_flops`` over the bf16 peak or its ``ssd_step_bytes`` over the
HBM peak, whichever is larger; ``bound(run)`` says which — over the device
time of the scope ``ssm_scan``, all phases.  ``ssm.scan_roofline`` for a
configuration of any key names (that reader imports ``flops_hybrid`` and
granite's keys); the module counts B and C at the groups the model has.
Defined by scope, so the XLA form of the scan (several groups today) and
kernels later read on one scale: ``ssm.kernel_ms`` says which form ran.

The structure's ceiling, as ``ssm.scan_roofline.py`` has it: the scope runs
the forward pass twice (the layer checkpoint keeps nothing of the scan), and
of the counted bytes a layer the forward is 2 x + B + C + dt of 5 x + 3 (B +
C + dt), so (5 x + 3 r) / (7 x + 4 r) is the most this structure can read
where the bytes bound it — 72.3 % at 64 heads x 64 with 8 groups of 128 —
and 75 % where the operations do.  A form that writes the (chunks, heads,
chunk, chunk) matrices to memory and reads them back stands far under that.
None where the trace has nothing under the scope or the module counts no
scan."""

from benchmark import flops, trace_scopes


def _least(run):
    conf, job = run["conf"], run["job"]
    count = flops.of(conf)
    return flops.roofline_seconds(
        count.ssd_step_flops(conf, job["rows"], job["seq"]),
        count.ssd_step_bytes(conf, job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    d = trace_scopes.device(run)
    scan_s = d and trace_scopes.scope_seconds(d, ("ssm_scan",))
    if not scan_s or not hasattr(flops.of(run["conf"]), "ssd_step_flops"):
        return None
    return 100.0 * _least(run)["seconds"] / scan_s
