"""A KDA mixer's projections in device milliseconds a step: the scopes
``kda_in`` (the block's norm and the ONE input projection, [q | k | v | the
two low ranks | b]: 4096 x 24896 columns in ``solaropen2-train-s4096``) and
``kda_out`` (the gated norm a head, the low-rank gate's up-projection, the
output projection 8192 x 4096, the add), all phases, all KDA layers — at an
inner width of twice the hidden size the largest part of that cell's step,
which no other metric reads.  None where the trace has nothing under the
two scopes."""

from benchmark import trace_scopes


def read(run):
    d = trace_scopes.device(run)
    if d is None:
        return None
    return 1e3 * trace_scopes.scope_seconds(d, ("kda_in", "kda_out")) or None
