"""Device milliseconds a step in the state-space scan's Mosaic kernels
(``ssd_fwd``, its rematerialised run, ``ssd_bwd``: ``ops/ssm.py``), all Mamba
layers: the counter that says WHICH FORM of the scan a cell measures.  0.0 —
a number, not None — where the step ran Mamba layers (time under
``ssm_scan``) and none of it in those kernels: the XLA form, which a model of
several groups runs today.  ``ssm.scan_ms`` less it is what XLA does round
the calls.

The trace reduction names a kernel by the prefixes it knows
(``trace_scopes.KERNELS`` and the configuration's ``"kernels"``: this
reader's cells list ``"ssd_"``).  None where the trace has nothing under the
scope (no Mamba layer, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if not (d and trace_scopes.scope_seconds(d, ("ssm_scan",))):
        return None
    return trace_scopes.kernel_ms(run, "ssd_") or 0.0
