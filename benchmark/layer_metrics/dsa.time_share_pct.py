"""Device self time under the scopes of learned sparse attention —
``dsa_index`` (the indexer's projections, norm, rotation and every
index-score product), ``dsa_select`` (the top-k and the mask), ``attention``
(the softmax over the selected pairs) and ``dsa_loss`` (the heads'
probabilities remade, the KL, its gradient) — all phases, every layer, as a
share of the traced steps' device time.  ``step.attention_pct`` holds the
scope ``attention`` too: this reader is the mechanism's whole cost, not one
more term of the sum to 100.  None where the trace has no ``dsa_*`` scope (a
program from before them, an untraced run)."""

from benchmark import trace_scopes

SCOPES = ("dsa_index", "dsa_select", "dsa_loss")


def read(run):
    if trace_scopes.step_share_pct(run, SCOPES) is None:
        return None
    return trace_scopes.step_share_pct(run, (*SCOPES, "attention"))
