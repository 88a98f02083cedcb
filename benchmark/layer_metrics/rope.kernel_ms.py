"""Device milliseconds a step in the rotation's Mosaic kernels (``rope_fwd``,
its rematerialised run, ``rope_bwd``: ``ops/rotary.py``), q's and k's of
every layer that rotates ``(b, s, heads x 128)``: the counter that says the
kernel ran in place of XLA's rolls, select and wide tables.

The trace reduction names a kernel by the prefixes it knows
(``trace_scopes.KERNELS`` and the configuration's ``"kernels"``); one it
does not know is ``unnamed``.  These kernels are the ``unnamed`` ones of
their cells, so both spellings are read.  The cells' other Mosaic kernels
are named (``flash_*``, ``moe_gmm*``, ``moe_tgmm``) but for the expert
layer's ``moe_row_buffer``, which writes nothing and reads 1.4e-5 ms a step
in a trace (Mellum2's cell at the parent of the PR that wrote the kernel):
a sum under a microsecond is no rotation.  None there and where the trace
has neither spelling (the XLA form, and every cell whose mixers rotate on
the 4-D view or not at all)."""

from benchmark import trace_scopes

_NO_ROTATION_MS = 1e-3


def read(run):
    times = [trace_scopes.kernel_ms(run, prefix)
             for prefix in ("rope_", "unnamed")]
    total = sum(t for t in times if t is not None)
    return total if total > _NO_ROTATION_MS else None
