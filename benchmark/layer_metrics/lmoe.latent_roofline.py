"""The least time the chip could take for the latent pair a step needs (the
configuration's FLOP module, ``flops.of(conf)``: ``latent_step_flops`` over
the bf16 peak or ``latent_step_bytes`` over the HBM peak, whichever is
larger — ``bound(run)`` says which) over the device time of the scope
``moe_latent``, all phases.  Defined by SCOPE: XLA runs the two products (a
side of each is the latent's 1024: ``(T, 4096) x (4096, 1024)`` and ``(T,
1024) x (1024, 4096)``), and a kernel later reads on the same scale.

Counted: every token through both matrices, forward, the gradient to the
input and the gradient to the matrix (6 a parameter and token), and for
each of those six products one read or write of the matrix, of the tokens
at the model's width and of the tokens at the latent's.  The structure's
ceiling: the rematerialised forward runs the down projection again (the
experts' input) but not the up projection (nothing of the backward reads
its output), so of seven products six are counted, 85.7 % where the
operations bound it — they do at 4096 tokens and six expert layers: 6.3
ms of operations against 2.2 ms of bytes a step —, and what XLA fuses
beside a product (the casts, the sum over ``sum_axes``) comes off that; the
v5e read 77.4 (PR 66).  None where the module counts no
latent pair or the trace has nothing under the scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "latent_step_flops"):
        return None
    return flops.roofline_seconds(
        count.latent_step_flops(run["conf"], job["rows"], job["seq"]),
        count.latent_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    latent_s = d and trace_scopes.scope_seconds(d, ("moe_latent",))
    least = _least(run)
    if not latent_s or least is None:
        return None
    return 100.0 * least["seconds"] / latent_s
