"""Device milliseconds a step in the KDA rule's Mosaic kernels (the prefix
the cell's configuration lists under ``"kernels"``: ``kdarule_``), all KDA
layers: the counter that says the kernels ran.  None where the trace has
none — the XLA form, which is what the rule runs as until its kernels are
written (``ray_tpu/ops/delta.py::kda_chunked``); ``kda.scan_ms`` is then
all XLA's."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "kdarule_")
