"""The least time the chip could take for the gated short convolutions a
step needs (the configuration's FLOP module, ``flops.of(conf)``:
``sconv_step_flops`` over the bf16 peak or ``sconv_step_bytes`` over the
HBM peak, whichever is larger — ``bound(run)`` says which; it is the
bytes by three orders of magnitude) over the device time of the scope
``sconv_gate``, all phases.  Defined by scope, so it reads the same whether
XLA or a Pallas kernel runs there.

Counted are the fewest reads and writes: forward B, C, x in and y out (4
arrays of tokens x hidden), backward B, C, x, dy in and dB, dC, dx out
(7): 369 MB a layer at 8192 x 2048 in bfloat16.  The structure's ceiling:
the scope runs the forward pass twice (the layer checkpoint keeps nothing
of the op), and the forward is 134 of the 369 MB a layer that are counted,
so 369 / 503 = 73 % is the most this structure can read.  None where the
module counts no short convolution or the trace has nothing under the
scope."""

from benchmark import flops, trace_scopes


def _least(run):
    count, job = flops.of(run["conf"]), run["job"]
    if not hasattr(count, "sconv_step_flops"):
        return None
    return flops.roofline_seconds(
        count.sconv_step_flops(run["conf"], job["rows"], job["seq"]),
        count.sconv_step_bytes(run["conf"], job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    least = _least(run)
    return least and least["bound"]


def read(run):
    d = trace_scopes.device(run)
    gate_s = d and trace_scopes.scope_seconds(d, ("sconv_gate",))
    least = _least(run)
    if not gate_s or least is None:
        return None
    return 100.0 * least["seconds"] / gate_s
