"""What XLA still does round the flash kernels, in device milliseconds a
step: the scope ``attention``, all phases, LESS the Mosaic kernels that run
there (``flash_*``: the windowed ``flash_*_win`` and rematerialised calls
too).  The scope holds the attention itself and nothing else — the
projections, RoPE and ``wo`` have scopes of their own — so this is what the
calls cost beside their kernels: operands turned round to the kernels'
layout and back, KV heads repeated up to the q heads' count and their
gradients summed back, the backward pass's ``delta`` and the stats'
lane broadcasts.  A program whose kernels read q, k, v where the model
leaves them keeps the last two alone.  0.0 where the kernels are all the
scope holds; None where the trace has nothing under the scope (a model
without attention layers, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    scope_s = d and trace_scopes.scope_seconds(d, ("attention",))
    if not scope_s:
        return None
    kernels_ms = trace_scopes.kernel_ms(run, "flash_") or 0.0
    # the kernels lie inside the scope: under 0 only by the sums' rounding
    return max(0.0, 1e3 * scope_s - kernels_ms)
