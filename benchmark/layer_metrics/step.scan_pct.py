"""Device self time of the layer scan's own ops (slicing a layer's weights
out of the stack, stacking its gradients and saved residuals: a ``while``
on the name stack and no scope) as a share of the traced steps' device
time."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, (trace_scopes.SCAN,))
