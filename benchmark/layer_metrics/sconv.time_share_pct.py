"""Device self time under a gated short-convolution mixer's three scopes
(``sconv_in``: norm and the [B | C | x] projection; ``sconv_gate``: ``C *
conv(B * x)``; ``sconv_out``: the output projection and the add; all
phases) as a share of the traced steps' device time: with the shared
``step.*_pct`` shares and ``moe.time_share_pct`` it makes 100.  None where
the trace has no such scope (a model without conv layers, or a program
from before the scopes)."""

from benchmark import trace_scopes

SCONV_SCOPES = ("sconv_in", "sconv_gate", "sconv_out")


def read(run):
    return trace_scopes.step_share_pct(run, SCONV_SCOPES)
