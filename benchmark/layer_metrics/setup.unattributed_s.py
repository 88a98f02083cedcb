"""Seconds of set-up's WALL under no span of the program: set-up's self
time.  The wall (process start of ``run.py`` -> the first measured step;
``setup_s`` until PR 62, which since then is this LESS the TPU runtime's
start and is no total of these books), less the length of the UNION,
clipped to ``[process_start, window_start]``, of the ``recent`` intervals
of every name in ``Result.metrics["_spans"]`` but the containers
(``train.fit``, ``train.run``, ``train.loop``: they hold the others and
say nothing about where the time went).  Overlapping and nested spans
count once, so ``covered(run)[0] + read(run)`` is the wall of the run.
What is left: the driver before ``fit()``, the program's imports, what
the device executes in set-up (state, check, warm-up) and the waits
between spans.

Nothing where ``device.bring_up`` is absent (a program whose loop opens
the chips: the largest piece would be filed as unattributed), or where a
name that began before the window has lost intervals — more spans than
``recent`` keeps and its oldest kept one later than its first: no number
rather than a wrong one."""

CONTAINERS = ("train.fit", "train.run", "train.loop")


def covered(run):
    """(seconds of set-up under some span, the wall); None as ``read``."""
    w = run["worker"]
    spans = w.get("_spans") or {}
    if "device.bring_up" not in spans:
        return None
    lo, hi = run["process_start"], w["window_start"]
    kept = []
    for name, s in spans.items():
        if name in CONTAINERS or s["first_start"] >= hi:
            continue
        recent = s.get("recent") or ()
        if s["count"] > len(recent) and (
                not recent or recent[0][0] > s["first_start"]):
            return None
        kept.extend((max(start, lo), min(end, hi)) for start, end in recent
                    if start < hi and end > lo)
    union, reach = 0.0, lo
    for start, end in sorted(kept):
        if end > reach:
            union += end - max(start, reach)
            reach = end
    return union, hi - lo


def read(run):
    got = covered(run)
    if got is None:
        return None
    union, wall = got
    return wall - union
