"""Device milliseconds a step in the delta rule's Mosaic kernels
(``delta_fwd``, its rematerialised run, ``delta_bwd``: ``ops/delta.py``),
all linear layers: the counter that says the kernels ran.  ``gdn.scan_ms``
less it is what XLA still does round the calls (the layouts the kernels
read, the chunks' cumulative log-decays and their gradient).

The trace reduction names a kernel by the prefixes it knows
(``trace_scopes.KERNELS`` and the configuration's ``"kernels"``); one it
does not know is ``unnamed``.  Until the cell's configuration lists
``"delta_"`` these kernels are the ``unnamed`` ones of their cell — its
only other Mosaic kernels are the flash ones, which are named — so both
spellings are read.  None where the trace has neither (the XLA form: the
parent of the PR that wrote the kernels)."""

from benchmark import trace_scopes


def read(run):
    times = [trace_scopes.kernel_ms(run, prefix)
             for prefix in ("delta_", "unnamed")]
    return sum(t for t in times if t is not None) or None
