"""Device milliseconds a step in the flash kernels WITHOUT a window
(``flash_fwd``, ``flash_dkv`` and their ``.remat`` twins) of a model with
differential attention: the full layer's and the cross layers' calls.  None
where the configuration's FLOP module counts no differential pairs or the
trace names no such kernel."""

from benchmark import flops, trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None or not hasattr(flops.of(run["conf"]), "pair_flops"):
        return None
    return 1e3 * sum(t for k, t in d["kernels"].items()
                     if k.startswith("flash_") and "_win" not in k) or None
