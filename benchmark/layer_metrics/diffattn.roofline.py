"""The least time the chip's matrix unit could take for the (q, k) pairs
differential attention NEEDS in a step (the configuration's FLOP module:
the window's pairs in a windowed layer, the causal ones in the full and the
cross layers, ``pair_flops`` a pair forward — two scores and two products
with the one doubled value head in each q pair — and twice that backward,
over the bf16 peak) over the device time of every ``flash_*`` kernel.  By
OPERATIONS alone, where ``flash_roofline`` takes the larger of operations
and bytes: at a head of 64 the kernels are bound by neither (the scores'
contraction fills half the matrix unit's depth), and this is the share that
says how far.  None where the module counts no differential pairs or no
flash kernel ran."""

from benchmark import flops, trace_scopes


def read(run):
    d, count = trace_scopes.device(run), flops.of(run["conf"])
    if d is None or not hasattr(count, "pair_flops"):
        return None
    flash_s = sum(t for k, t in d["kernels"].items()
                  if k.startswith("flash_"))
    if not flash_s:
        return None
    job = run["job"]
    needed = count.flash_step_flops(run["conf"], job["rows"], job["seq"])
    return 100.0 * needed / run["peak"]["bf16_flops_per_s"] / flash_s
