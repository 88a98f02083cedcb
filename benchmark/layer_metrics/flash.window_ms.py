"""Device milliseconds a step in the WINDOWED flash kernels (the Mosaic
kernels ``flash_fwd_win``, ``flash_dq_win``, ``flash_dkv_win`` and their
``.remat`` twins: attention under a sliding window, which skips the tiles
beyond either edge), all windowed layers; on a mesh, the chip whose steps
took longest.  ``flash.fwd_ms`` / ``.dq_ms`` / ``.dkv_ms`` hold them too,
beside the plain kernels of the full layers.  None where the trace names no
such kernel (a program without the window, a cell without a windowed
layer)."""

from benchmark import trace_scopes


def read(run):
    return sum(trace_scopes.kernel_ms(run, f"flash_{kernel}_win") or 0.0
               for kernel in ("fwd", "dq", "dkv")) or None
