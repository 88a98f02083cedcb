"""The router of the expert layers in device milliseconds a step: the scope
``moe_route`` (the block's norm, the router's logits over ALL the published
experts in float32, the sigmoid or softmax, ``lax.top_k`` of the scores
plus the selection bias, the gates' renormalisation, the experts' counts by
a compare and a sum, the losses), all phases, every expert layer.
``moe.dispatch_ms`` reads it together with the sort and the gathers; alone
it is what 22 choices over 512 experts cost, which no older cell comes near
(top-4 to top-8 over 32 to 320).  None where the trace has nothing under
the scope (a dense model, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("moe_route",)) or None
