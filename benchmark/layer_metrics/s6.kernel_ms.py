"""Device milliseconds a step in the selective scan's Mosaic kernels
(``selscan_fwd``, its rematerialised run, ``selscan_bwd``:
``ray_tpu/ops/ssm.py``), all Mamba-1 layers: the counter that says WHICH
FORM of the scan a cell measures.  ``s6.scan_ms`` less it is what XLA does
round the calls.  None — the metric is left out — where the trace names no
such kernel: the XLA form ran, or no Mamba-1 layer, or the run was not
traced.  (The cell's configuration lists the prefix ``selscan_``.)"""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "selscan_")
