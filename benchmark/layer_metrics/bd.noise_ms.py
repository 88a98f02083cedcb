"""Device milliseconds a step under the scope ``bd_noise``: what the
block-diffusion objective adds in front of the model — the step's key, the
draw of ``t`` and of a uniform a position, the corruption, the join of the
noised and the clean stream and the loss weights ``m / p`` —, all phases.
None where the trace has nothing there."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("bd_noise",)) or None
