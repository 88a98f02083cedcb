#!/usr/bin/env python3
"""Which steps of a run came late, and whose fault each was.

    python benchmark/late_steps.py <details.json> [--min-ms 1.0]

``<details.json>`` is what ``benchmark/run.py --details <file>`` wrote of
ANY run, traced or not.  Prints the numbers the readers ``host.late_ms``,
``host.late_stopped_ms``, ``host.late_running_ms``,
``host.late_waiting_ms``, ``host.involuntary_switches``,
``worker.flush_ms`` and ``setup.lag_s`` give (the same arithmetic:
``benchmark/lost_time.py``, whose header says what STOPPED, RUNNING and
WAITING mean), then one row for each interval between two reports of the
measured window that is late by ``--min-ms`` or more: the report that came
late (its ``_training_iteration`` / its place in the window, from 0),
milliseconds late and their three parts, the loop thread's CPU time,
voluntary and involuntary context switches and major page faults in the
interval, and the process-wide spans (``host.lag``, ``gc.pause``,
``worker.flush``) that lie in it, ``name x count = ms``.

The run to point it at: the one of a pair that read 4-8 % low.  STOPPED
says the machine's neighbour or a signal had the worker off the CPU (or a
thread of its own held the interpreter): pin and quieten the worker.
RUNNING says the loop's own Python was busy.  WAITING says the loop sat
in the fetch: the device or its runtime was slow, and only a traced run
shows the device's side.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lost_time  # noqa: E402


def report(run: dict, min_ms: float = 1.0) -> str:
    total = lost_time.totals(run)
    if total is None:
        return ("nothing to read: the program's session.report keeps no "
                "clock, or the window has under 3 intervals")
    ms = {k: total[k + "_ms"] for k in ("late", "stopped", "running",
                                        "waiting")}
    lag = lost_time.setup_lag(run)
    out = [
        f"window: {total['intervals']} intervals, median "
        f"{1e3 * total['median_interval_s']:.3f} ms, of which the loop "
        f"thread on the CPU {1e3 * total['median_thread_cpu_s']:.3f} ms",
        f"host.late_ms {ms['late']:.3f} = stopped {ms['stopped']:.3f} "
        f"+ running {ms['running']:.3f} + waiting {ms['waiting']:.3f}",
        f"host.involuntary_switches {total['involuntary']}  (voluntary "
        f"{total['voluntary']}, major faults {total['major_faults']})",
        f"worker.flush_ms {1e3 * lost_time.flush_s(run):.3f}",
        f"loop thread's CPU {total['thread_cpu_s']:.4f} s, the process's "
        f"other threads' {total['other_threads_cpu_s']:.4f} s",
    ]
    if lag is None:
        out.append("setup.lag_s: not read (host.lag lost intervals)")
    else:
        inside = ", ".join(f"{name} {s:.3f}" for name, s in sorted(
            lag["inside"].items(), key=lambda kv: -kv[1])) or "none"
        out.append(f"setup.lag_s {lag['total_s']:.3f}  (inside: {inside}; "
                   f"under no span {lag['outside_s']:.3f})")
    rows = [r for r in lost_time.intervals(run)
            if 1e3 * r["late_s"] >= min_ms]
    out.append(f"{len(rows)} interval(s) late by {min_ms:g} ms or more")
    if rows:
        out.append(f"{'report':>11}{'late ms':>10}{'stopped':>10}"
                   f"{'running':>10}{'waiting':>10}{'cpu ms':>9}{'vol':>6}"
                   f"{'invol':>6}{'majflt':>7}  process-wide spans")
    for r in rows:
        spans = ", ".join(f"{name} x {n} = {1e3 * s:.1f}"
                          for name, (n, s) in r["spans"].items())
        out.append(
            f"{r['report']:>7}/{r['in_window']:<3}"
            + "".join(f"{1e3 * r[k]:>10.3f}" for k in (
                "late_s", "stopped_s", "running_s", "waiting_s"))
            + f"{1e3 * r['thread_cpu_s']:>9.3f}{r['voluntary']:>6}"
              f"{r['involuntary']:>6}{r['major_faults']:>7}  {spans}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("details", help="a file run.py --details wrote")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="rows for intervals late by this much or more")
    args = ap.parse_args(argv)
    with open(args.details) as f:
        run = json.load(f)
    print(report(run, args.min_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
