"""Device milliseconds a step in the PLAIN flash kernels (the Mosaic kernels
``flash_fwd``, ``flash_dq``, ``flash_dkv`` and their ``.remat`` twins) with
the windowed ones (``flash_*_win``, ``flash.window_ms``) taken out: what the
``full_attention`` layers' attention costs beside the windowed layers' in a
model that has both; on a mesh, the chip whose steps took longest.  None
where the trace names no flash kernel or every one of them is windowed."""

from benchmark import trace_scopes


def read(run):
    ms = 0.0
    for kernel in ("fwd", "dq", "dkv"):
        ms += (trace_scopes.kernel_ms(run, f"flash_{kernel}") or 0.0) - (
            trace_scopes.kernel_ms(run, f"flash_{kernel}_win") or 0.0)
    # the windowed kernels are among the first sum's: under 0 only by the
    # sums' rounding
    return ms if ms > 1e-9 else None
