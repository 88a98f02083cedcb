"""A window's lost time, and whose fault it was: the arithmetic the readers
``host.late_*``, ``host.involuntary_switches``, ``worker.flush_ms`` and
``setup.lag_s`` share with ``benchmark/late_steps.py``.

All of it from ``Result.metrics["_spans"]``, which EVERY run carries
(``run.py --details`` writes it):

- ``session.report``'s ``recent`` — the ``(start, end)`` of the last 256
  reports, one a step, each after the fetch of that step's loss — and,
  beside it, ``clock``: the loop thread's ``(thread CPU s, process CPU s,
  voluntary switches, involuntary switches, major faults)`` at each
  report's start, cumulative (``ray_tpu.util.tracing.thread_clock``);
- the process-wide spans ``host.lag`` (no Python thread of the worker could
  run: the lag meter woke over 20 ms late), ``gc.pause`` and
  ``worker.flush`` (the worker's periodic thread, an iteration over 1 ms).

An INTERVAL is the time between two neighbouring reports' starts in the
measured window ``[window_start, window_start + elapsed_s]``, as
``host.step_max_over_median`` reads it; it is LATE by ``interval - median``
where that is positive.  What an interval is late by is split three ways,
in this order:

- STOPPED: what ``host.lag`` and ``gc.pause`` spans cover of it (their
  union clipped to the interval), no more than it is late by — the loop
  runs a step ahead of the device, so a stop may cost less than it lasted;
- RUNNING: of the rest, the loop thread's CPU time in the interval beyond
  the median interval's — the host's Python was busy;
- WAITING: the remainder — the loop thread was off the CPU of its own
  accord: in the fetch, waiting for the device or the runtime under it.

A window's ``late_ms`` is the SUM of its three parts in milliseconds, so
they make it to the float.  Nothing (``None``) from a program that keeps
no ``clock``, or from a window of fewer than 3 intervals.
"""

import statistics

STOPPERS = ("host.lag", "gc.pause")  # no thread of the worker could run
PROCESS_WIDE = STOPPERS + ("worker.flush",)
CLOCK = ("thread_cpu_s", "process_cpu_s", "voluntary", "involuntary",
         "major_faults")


def _spans(run):
    return run["worker"].get("_spans") or {}


def _recent(run, name):
    return [tuple(p) for p in (_spans(run).get(name) or {}).get("recent", ())]


def window(run):
    w = run["worker"]
    lo = w["window_start"]
    return lo, lo + w["window"]["elapsed_s"]


def reports(run):
    """The window's reports, oldest first: ``(index, start, clock)`` —
    ``index`` is the report's ``_training_iteration``.  None where the
    program keeps no ``clock``."""
    report = _spans(run).get("session.report") or {}
    if "clock" not in report or "recent" not in report:
        return None
    lo, hi = window(run)
    first = report["count"] - len(report["recent"])
    return [(first + i, start, tuple(clock)) for i, ((start, _), clock)
            in enumerate(zip(report["recent"], report["clock"]))
            if lo <= start <= hi]


def covered(spans, lo, hi):
    """Seconds of ``[lo, hi]`` under the union of ``spans``."""
    union, reach = 0.0, lo
    for start, end in sorted(spans):
        start, end = max(start, reach), min(end, hi)
        if end > start:
            union += end - start
            reach = end
    return union


def intervals(run):
    """One row an interval of the window, oldest first (the module's
    header); None as the header says."""
    got = reports(run)
    if got is None or len(got) < 4:
        return None
    wide = {name: _recent(run, name) for name in PROCESS_WIDE}
    stoppers = [p for name in STOPPERS for p in wide[name]]
    pairs = list(zip(got, got[1:]))
    gaps = [b[1] - a[1] for a, b in pairs]
    cpus = [b[2][0] - a[2][0] for a, b in pairs]
    usual, usual_cpu = statistics.median(gaps), statistics.median(cpus)
    rows = []
    for n, ((_, lo, was), (index, hi, now)) in enumerate(pairs):
        late = max(0.0, gaps[n] - usual)
        stopped = min(covered(stoppers, lo, hi), late)
        running = min(late - stopped, max(0.0, cpus[n] - usual_cpu))
        rows.append({
            "report": index, "in_window": n + 1, "start": lo,
            "interval_s": gaps[n], "late_s": late, "stopped_s": stopped,
            "running_s": running,
            "waiting_s": late - stopped - running,
            **{k: b - a for k, a, b in zip(CLOCK, was, now)},
            "spans": {name: [sum(1 for s, _ in spans if lo <= s < hi),
                             covered(spans, lo, hi)]
                      for name, spans in wide.items()
                      if any(s < hi and e > lo for s, e in spans)}})
    return rows


def totals(run):
    """The window's lost time in MILLISECONDS, as the readers give it —
    ``late_ms``, the sum of its parts ``stopped_ms``, ``running_ms``,
    ``waiting_ms`` —, the median interval and its median CPU time, and the
    loop thread's clock over the window (``CLOCK``'s names;
    ``other_threads_cpu_s``: the process's CPU less the loop thread's —
    the lag meter, the periodic thread and the runtime's own).  None as
    ``intervals``."""
    rows = intervals(run)
    if rows is None:
        return None
    out = {k: sum(r[k] for r in rows) for k in CLOCK}
    for part in ("stopped", "running", "waiting"):
        out[part + "_ms"] = 1e3 * sum(r[part + "_s"] for r in rows)
    out["late_ms"] = out["stopped_ms"] + out["running_ms"] + out["waiting_ms"]
    out["intervals"] = len(rows)
    out["median_interval_s"] = statistics.median(
        r["interval_s"] for r in rows)
    out["median_thread_cpu_s"] = statistics.median(
        r["thread_cpu_s"] for r in rows)
    out["other_threads_cpu_s"] = out["process_cpu_s"] - out["thread_cpu_s"]
    return out


def flush_s(run):
    """Seconds of ``worker.flush`` spans that start in the window; None
    from a program that keeps no ``clock`` (it has no such span)."""
    if reports(run) is None:
        return None
    lo, hi = window(run)
    return sum(e - s for s, e in _recent(run, "worker.flush")
               if lo <= s <= hi)


def setup_lag(run):
    """``host.lag`` before the window: ``{"total_s", "inside": {name:
    seconds}, "outside_s"}`` — the lags between the process start and
    ``window_start``, what of them lies inside each of the program's spans
    that a lag overlaps (nested names each count what they cover), and
    what lies under no span but the containers.  None from a program that
    keeps no ``clock`` (it has no lag meter), or where ``host.lag`` lost
    intervals from before the window (more lags than ``recent`` keeps)."""
    spans = _spans(run)
    if reports(run) is None:
        return None
    lo, hi = run["process_start"], run["worker"]["window_start"]
    lag, recent = spans.get("host.lag"), _recent(run, "host.lag")
    if lag and lag["count"] > len(recent) and lag["first_start"] < hi \
            and (not recent or recent[0][0] > lag["first_start"]):
        return None
    lags = [(max(s, lo), min(e, hi)) for s, e in recent if s < hi and e > lo]
    containers = ("train.fit", "train.run", "train.loop", *PROCESS_WIDE)
    inside, under_any = {}, []
    for name in spans:
        if name in containers:
            continue
        theirs = _recent(run, name)
        part = sum(covered(theirs, a, b) for a, b in lags)
        if part > 0:
            inside[name] = part
            under_any.extend(theirs)
    total = sum(b - a for a, b in lags)
    return {"total_s": total, "inside": inside,
            "outside_s": total - sum(covered(under_any, a, b)
                                     for a, b in lags)}
