"""The least time the chip could take for the n-stream residual a step
needs (the FLOP module the configuration names, ``benchmark/flops.py::of``:
its ``hc_step_flops`` over the bf16 peak or its ``hc_step_bytes`` — the
fewest reads and writes of the streams — over the HBM peak, whichever is
larger; ``bound(run)`` says which) over the device time of the scopes
``hc_map`` AND ``hc_mix``, all phases.  Both scopes, because the least
count reads the streams once for the maps and the block's input together:
a compiler that fuses the two attributes the read to either scope, and a
share over ``hc_mix`` alone could then pass 100.  Defined by scope, so it
reads the same whether XLA or a kernel runs there.

The structure's ceiling: the layer checkpoint runs the forward pass twice
and the count has it once, so of 12 n d + 7 d numbers moved a token and
block 9 n d + 5 d are counted: 75.0 % at n = 4 is the most this structure
can read where the bytes bound it."""

from benchmark import flops, trace_scopes


def _least(run):
    conf, job = run["conf"], run["job"]
    count = flops.of(conf)
    return flops.roofline_seconds(
        count.hc_step_flops(conf, job["rows"], job["seq"]),
        count.hc_step_bytes(conf, job["rows"], job["seq"]),
        run["peak"])


def bound(run):
    return _least(run)["bound"]


def read(run):
    d = trace_scopes.device(run)
    hc_s = d and trace_scopes.scope_seconds(d, ("hc_map", "hc_mix"))
    if not hc_s or not hasattr(flops.of(run["conf"]), "hc_step_bytes"):
        return None
    return 100.0 * _least(run)["seconds"] / hc_s
