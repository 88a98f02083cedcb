"""The longest time between two neighbouring reports of the measured
window over the median one: 1.0 where the host saw every step take the
same time, about 2.5 where one step of 0.8 s stalled for 1.2 s.  The
reports are the starts of the program's ``session.report`` spans (one a
step, each after the fetch of that step's loss) that lie in
``[window_start, window_start + elapsed_s]``, from ``recent`` of
``Result.metrics["_spans"]``: the last 256 ``(start, end)`` of a name.
``stalled(run)`` names the interval.  Nothing under 3 intervals, or from a
program that keeps no ``recent``."""

import statistics


def _intervals(run):
    w = run["worker"]
    report = (w.get("_spans") or {}).get("session.report") or {}
    if "recent" not in report:
        return None
    lo = w["window_start"]
    hi = lo + w["window"]["elapsed_s"]
    starts = [start for start, _ in report["recent"] if lo <= start <= hi]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return gaps if len(gaps) >= 3 else None


def stalled(run):
    """(which of the window's reports came latest after the one before it,
    counted from 0; seconds between the two); None as ``read``."""
    gaps = _intervals(run)
    if gaps is None:
        return None
    longest = max(range(len(gaps)), key=gaps.__getitem__)
    return longest + 1, gaps[longest]


def read(run):
    gaps = _intervals(run)
    if gaps is None:
        return None
    return max(gaps) / statistics.median(gaps)
