"""What XLA still does round the grouped kernels, in device milliseconds a
step: the scope ``moe_experts``, all phases, LESS the Mosaic kernels that
run there (``moe_gmm*`` and ``moe_tgmm*``, rematerialised calls too).  The
routed experts' FFN is one rule whose products, SwiGLU, its derivative and
the sum of the rows' two cotangents all sit inside those kernels
(``ops/moe.py::expert_ffn``), so this is the counter that says the rule
engaged: what is left are casts of the weights' gradients, if anything.  A
program that runs SwiGLU and the sum as passes over the whole static row
buffer reads them here.  None where the trace has nothing under the scope
(a dense model, an untraced run)."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    scope_s = d and trace_scopes.scope_seconds(d, ("moe_experts",))
    if not scope_s:
        return None
    kernels_ms = sum(trace_scopes.kernel_ms(run, prefix) or 0.0
                     for prefix in ("moe_gmm", "moe_tgmm"))
    # the kernels lie inside the scope: under 0 only by the sums' rounding
    return max(0.0, 1e3 * scope_s - kernels_ms)
