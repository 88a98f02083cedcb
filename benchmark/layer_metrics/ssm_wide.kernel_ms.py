"""Device milliseconds a step in the state-space scan's Mosaic kernels
(``ssd_fwd``, its rematerialised run, ``ssd_bwd``: ``ops/ssm.py``), all Mamba
layers, in the cell whose mixer has 128 heads x 64 (inner width 8192, twice
the hidden size) in 8 groups.  ONE QUANTITY UNDER TWO NAMES: this is what
``ssm.kernel_ms`` reads (0.0 — a number, not None — where the step ran
Mamba layers and none of it in those kernels: the XLA form), under a name
of its own because that entry's list of cells is held by equality
(``benchmark/tests/test_nemotron_h.py``); a ``benchmark`` PR folds the two
(``PERF.md`` section 7, From PR 64 (a)).  The cell's configuration lists the
prefix ``"ssd_"``.  None where the trace has nothing under ``ssm_scan``."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if not (d and trace_scopes.scope_seconds(d, ("ssm_scan",))):
        return None
    return trace_scopes.kernel_ms(run, "ssd_") or 0.0
