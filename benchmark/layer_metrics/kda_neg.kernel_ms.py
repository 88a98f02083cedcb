"""Device milliseconds a step in the KDA rule's Mosaic kernels (the prefix
the cell's configuration lists under ``"kernels"``: ``kdarule_``), all KDA
layers, in the cell whose write strength reaches 2: the counter that says
the kernels ran there (``kda.kernel_ms``'s quantity under a name of its
own: that entry's list is held by equality).  None where the trace has none
— the XLA form ``ray_tpu/ops/delta.py::kda_xla``, or a program without the
rule."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.kernel_ms(run, "kdarule_")
