"""Device self time under a KDA mixer's four scopes (``kda_in``,
``kda_conv``, ``kda_scan``, ``kda_out``; all phases) as a share of the
traced steps' device time, in the cell whose write strength reaches 2
(``solaropen2-train-s4096``: 3 layers x 64 heads x 128 over a hidden size
of 4096): with the shared ``step.*_pct`` shares and ``moe.time_share_pct``
it makes 100.  ``kda.time_share_pct``'s quantity under a name of its own,
because that entry's list of cells is held by equality
(``benchmark/tests/test_kimi_linear.py``).  None where the trace has no
such scope (a program from before the scopes)."""

from benchmark import trace_scopes

KDA_SCOPES = ("kda_in", "kda_conv", "kda_scan", "kda_out")


def read(run):
    return trace_scopes.step_share_pct(run, KDA_SCOPES)
