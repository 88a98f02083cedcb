"""The readers of a window's lost time (PR 68) on canned ``_spans``:
``host.late_ms`` and its three parts, ``host.involuntary_switches``,
``worker.flush_ms``, ``setup.lag_s``; their shared arithmetic
(``benchmark/lost_time.py``) and the tool ``benchmark/late_steps.py``.
What they read is ``clock`` beside ``recent`` of ``session.report`` — the
loop thread's ``(thread CPU s, process CPU s, voluntary switches,
involuntary switches, major faults)`` at each report's start — and the
process-wide spans ``host.lag``, ``gc.pause`` and ``worker.flush``.  The
parent commit's ``_spans`` has no ``clock``: every reader gives ``None``.
The same readers on stalls MADE in a live process:
``tests/test_tracing.py``."""

import importlib.util
import json
import os

import pytest

from benchmark import late_steps, lost_time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAMES = ("host.late_ms", "host.late_stopped_ms", "host.late_running_ms",
         "host.late_waiting_ms", "host.involuntary_switches",
         "worker.flush_ms", "setup.lag_s")
UNITS = ("ms", "ms", "ms", "ms", "count", "ms", "s")


def _read(name, run):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _span(recent, **more):
    return {"count": len(recent), "total_s": sum(e - s for s, e in recent),
            "max_s": max((e - s for s, e in recent), default=0.0),
            "first_start": recent[0][0], "last_end": recent[-1][1],
            "recent": recent, **more}


def _run(starts, cpu, spans=None, switches=None, window_start=1000.0,
         elapsed_s=10.0):
    """Reports at ``starts``; ``cpu[i]``: the loop thread's CPU seconds,
    cumulative, at report ``i``; ``switches[i]``: (voluntary, involuntary,
    major faults), cumulative."""
    switches = switches or [(i, 0, 0) for i in range(len(starts))]
    report = _span([(t, t + 2e-4) for t in starts],
                   clock=[(c, 2 * c, *s) for c, s in zip(cpu, switches)])
    return {"process_start": 900.0,
            "worker": {"_spans": {"session.report": report, **(spans or {})},
                       "window_start": window_start,
                       "window": {"elapsed_s": elapsed_s}}}


# Two warm-up reports, twelve of the window half a second apart (binary
# fractions: every interval is the same to the bit), one after it.
STEADY = [990.0, 995.0] + [1000.5 + 0.5 * i for i in range(12)] + [1013.0]
STEADY_CPU = [0.5, 0.75] + [1.0 + 0.0078125 * i for i in range(12)] + [3.0]


def test_a_steady_window_reads_zero_everywhere():
    run = _run(STEADY, STEADY_CPU)
    assert [_read(name, run) for name in NAMES] == [0.0] * 7
    assert all(r["late_s"] == 0.0 for r in lost_time.intervals(run))
    total = lost_time.totals(run)
    assert total["intervals"] == 11 and total["median_interval_s"] == 0.5
    assert total["median_thread_cpu_s"] == 0.0078125
    assert total["thread_cpu_s"] == total["other_threads_cpu_s"] == 0.0859375
    assert "0 interval(s) late by 1 ms or more" in late_steps.report(run)


# The window's reports 0.5 s apart, but: the 5th 1.7 s late, of which a
# lag covers 1.5 (it lasted 2.0: a step was in hand) — and the thread ran
# 0.125 s of CPU over its usual; the 9th 0.25 s late with the thread off
# the CPU throughout.  A pause of the collector in an interval that is not
# late counts for nothing; a flush of 3 ms lies in the window, one before.
def _stalled():
    starts, cpu, t, c = [990.0, 995.0], [0.5, 0.75], 1000.0, 1.0
    for i in range(12):
        t += 0.5 + {4: 1.75, 8: 0.25}.get(i, 0.0)
        c += 0.0078125 + (0.125 if i == 4 else 0.0)
        starts.append(t)
        cpu.append(c)
    switches = [(i, 7 if i >= 6 else 0, 2 if i >= 6 else 0)
                for i in range(len(starts))]
    spans = {
        "host.lag": _span([(950.0, 950.25), (1002.25, 1003.75)]),
        "gc.pause": _span([(1001.0, 1001.0625), (1003.5, 1003.875)]),
        "worker.flush": _span([(999.0, 999.5), (1002.5, 1002.503)]),
        "jax.backend_init": _span([(949.875, 950.125)]),
        "device.bring_up": _span([(949.0, 950.125)]),
        "train.loop": _span([(940.0, 1020.0)]),
    }
    return _run(starts + [1013.0], cpu + [3.0], spans,
                switches + [(99, 9, 9)], elapsed_s=12.0)


def test_a_stall_is_split_into_stopped_running_and_waiting():
    run = _stalled()
    rows = lost_time.intervals(run)
    assert [r["report"] for r in rows] == list(range(3, 14))
    late = {r["in_window"]: r for r in rows if r["late_s"] > 0}
    assert sorted(late) == [4, 8]
    # lag and pause overlap: their UNION, 1002.25-1003.875, is 1.625 s
    # of an interval 1.75 s late
    assert late[4]["late_s"] == 1.75 and late[4]["stopped_s"] == 1.625
    assert late[4]["running_s"] == 0.125 and late[4]["waiting_s"] == 0.0
    assert late[4]["spans"] == {"host.lag": [1, 1.5],
                                "gc.pause": [1, 0.375],
                                "worker.flush": [1, pytest.approx(0.003)]}
    assert (late[4]["involuntary"], late[4]["major_faults"]) == (7, 2)
    assert late[8]["late_s"] == 0.25 == late[8]["waiting_s"]
    assert late[8]["stopped_s"] == late[8]["running_s"] == 0.0
    assert late[8]["spans"] == {}
    assert _read("host.late_ms", run) == 2000.0
    assert _read("host.late_stopped_ms", run) == 1625.0
    assert _read("host.late_running_ms", run) == 125.0
    assert _read("host.late_waiting_ms", run) == 250.0
    assert _read("host.involuntary_switches", run) == 7
    assert _read("worker.flush_ms", run) == pytest.approx(3.0)
    # the lag of set-up: 0.25 s, half of it inside jax.backend_init (and
    # so inside device.bring_up), half under no span but the loop's
    assert _read("setup.lag_s", run) == 0.25
    lag = lost_time.setup_lag(run)
    assert lag == {"total_s": 0.25, "outside_s": 0.125, "inside": {
        "jax.backend_init": 0.125, "device.bring_up": 0.125}}


def test_a_stop_longer_than_the_interval_is_late_by_counts_for_no_more():
    starts = [1000.5 + 0.5 * i for i in range(6)]
    starts += [starts[-1] + 1.0 + 0.5 * i for i in range(6)]
    cpu = [0.0078125 * i for i in range(12)]
    # the process stood for 0.875 s; the device had a step in hand, and
    # the report came 0.5 s late
    run = _run(starts, cpu, {"host.lag": _span([(1003.0625, 1003.9375)])})
    assert _read("host.late_ms", run) == 500.0
    assert _read("host.late_stopped_ms", run) == 500.0
    assert _read("host.late_running_ms", run) == 0.0
    assert _read("host.late_waiting_ms", run) == 0.0


@pytest.mark.parametrize("seed", range(4))
def test_the_three_parts_make_late_ms_to_the_float(seed):
    import random

    rng = random.Random(seed)
    starts, cpu, t, c = [], [], 1000.0, 0.0
    for _ in range(40):
        t += 0.3 + rng.random() * 0.05 + (rng.random() < 0.2) * rng.random()
        c += 0.004 * rng.random() + (rng.random() < 0.2) * 0.1 * rng.random()
        starts.append(t)
        cpu.append(c)
    lags = [(s + 0.1 * rng.random(), s + 0.1 + 0.4 * rng.random())
            for s in rng.sample(starts, 6)]
    run = _run(starts, cpu, {"host.lag": _span(sorted(lags))},
               elapsed_s=60.0)
    late, stopped, running, waiting = (_read(n, run) for n in NAMES[:4])
    assert stopped + running + waiting == late  # ==, not approx
    assert min(stopped, running, waiting) >= 0.0 and stopped > 0.0
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    usual = sorted(gaps)[len(gaps) // 2]
    assert late == pytest.approx(
        1e3 * sum(max(0.0, g - usual) for g in gaps), rel=1e-9)
    # through JSON (``--details``: lists for tuples) nothing changes
    again = json.loads(json.dumps(run))
    assert [_read(n, again) for n in NAMES] == [_read(n, run) for n in NAMES]


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_read_nothing(name):
    parent = _run(STEADY, STEADY_CPU, {
        "gc.pause": _span([(1001.0, 1001.5)]),
        "device.bring_up": _span([(949.0, 950.125)])})
    del parent["worker"]["_spans"]["session.report"]["clock"]
    assert _read(name, parent) is None
    assert _read(name, {"process_start": 0.0, "worker": {
        "_spans": None, "window_start": 1.0,
        "window": {"elapsed_s": 1.0}}}) is None  # a failed session
    assert "nothing to read" in late_steps.report(parent)
    if name not in ("worker.flush_ms", "setup.lag_s"):
        # three reports in the window make two intervals: too few
        few = _run(STEADY[:5] + STEADY[-1:], STEADY_CPU[:5] + [3.0])
        assert _read(name, few) is None


def test_setup_lag_gives_no_number_where_lags_were_lost():
    recent = [(1005.0 + i, 1005.5 + i) for i in range(3)]
    lost = dict(_span(recent), count=300, first_start=950.0)
    run = _run(STEADY, STEADY_CPU, {"host.lag": lost})
    assert _read("setup.lag_s", run) is None
    assert "setup.lag_s: not read" in late_steps.report(run)
    # all of the lost ones fell after the window began: nothing is missing
    kept = dict(_span(recent), count=300, first_start=1000.25)
    assert _read("setup.lag_s", _run(STEADY, STEADY_CPU,
                                     {"host.lag": kept})) == 0.0


def test_the_tool_prints_the_readers_numbers_and_a_row_a_late_interval(
        tmp_path, capsys):
    path = tmp_path / "details.json"
    path.write_text(json.dumps(_stalled()))
    assert late_steps.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert ("host.late_ms 2000.000 = stopped 1625.000 + running 125.000 "
            "+ waiting 250.000") in out
    assert "host.involuntary_switches 7 " in out
    assert "worker.flush_ms 3.000" in out
    assert "setup.lag_s 0.250  (inside: " in out
    assert "jax.backend_init 0.125" in out and "under no span 0.125" in out
    assert "2 interval(s) late by 1 ms or more" in out
    rows = [line.split() for line in out.splitlines()
            if line.strip().startswith(("6/4", "10/8"))]
    assert [r[0] for r in rows] == ["6/4", "10/8"]
    assert rows[0][1:5] == ["1750.000", "1625.000", "125.000", "0.000"]
    assert rows[1][1:5] == ["250.000", "0.000", "0.000", "250.000"]
    assert "host.lag x 1 = 1500.0" in out and "gc.pause x 1 = 375.0" in out
    assert late_steps.main([str(path), "--min-ms", "300"]) == 0
    assert "1 interval(s) late by 300 ms" in capsys.readouterr().out


def test_the_seven_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    first = names.index(NAMES[0])  # side by side, after what the file had
    assert names[first:first + len(NAMES)] == list(NAMES)
    assert all(names.count(n) == 1 for n in NAMES)
    older_layers = {m["layer"] for m in entries[:first]}
    for m, unit in zip(entries[first:first + len(NAMES)], UNITS):
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}  # no ``workloads``: every cell
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, "lower", "program_counter", "host loop"), m["name"]
        assert m["layer"] in older_layers
        assert m["moves"] == ("setup_s" if m["name"] == "setup.lag_s"
                              else "train_tokens_per_s")
