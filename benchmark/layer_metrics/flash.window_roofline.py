"""The least time a chip could take for the attention of the WINDOWED layers
a step needs (the configuration's FLOP module, ``flops.of(conf)``:
``window_step_flops`` — the pairs the window leaves, never the causal ones —
over the bf16 peak or ``window_step_bytes`` over the HBM peak, whichever is
larger; ``bound(run)`` says which) over the device time of the kernels
``flash_*_win`` (``flash.window_ms``): the windowed kernels' share of their
roofline.  None where the module counts no window or the trace names no such
kernel."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "window_step_flops"):
        return None
    return flops.roofline_seconds(
        count.window_step_flops(run["conf"], job["rows"], job["seq"])
        / run["chips"],
        count.window_step_bytes(run["conf"], job["rows"], job["seq"])
        / run["chips"],
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    ms = sum(trace_scopes.kernel_ms(run, f"flash_{kernel}_win") or 0.0
             for kernel in ("fwd", "dq", "dkv"))
    least = _least(run)
    if not ms or least is None:
        return None
    return 100.0 * least["seconds"] / (ms * 1e-3)
