"""Milliseconds of the measured window in which Python's garbage
collector had every thread of the worker stopped: the program's
``gc.pause`` spans (every collection of generation 2, any other over 1 ms)
that START in ``[window_start, window_start + elapsed_s]``, summed, from
``recent`` of ``Result.metrics["_spans"]``.  0.0 where the program keeps
``recent`` and no pause fell in the window; nothing from a program that
keeps none."""


def read(run):
    w = run["worker"]
    spans = w.get("_spans") or {}
    if "recent" not in (spans.get("session.report") or {}):
        return None
    lo = w["window_start"]
    hi = lo + w["window"]["elapsed_s"]
    pauses = (spans.get("gc.pause") or {}).get("recent", ())
    return 1e3 * sum(end - start for start, end in pauses
                     if lo <= start <= hi)
