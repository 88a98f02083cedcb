"""Device milliseconds a step in the WINDOWED flash kernels
(``flash_fwd_win``, ``flash_dkv_win`` and their ``.remat`` twins) of a model
with differential attention: its windowed layers' calls, 40 heads on 20 at
64 / 128.  None where the configuration's FLOP module counts no
differential pairs or the trace names no such kernel."""

from benchmark import flops, trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None or not hasattr(flops.of(run["conf"]), "pair_flops"):
        return None
    return 1e3 * sum(t for k, t in d["kernels"].items()
                     if k.startswith("flash_") and "_win" in k) or None
