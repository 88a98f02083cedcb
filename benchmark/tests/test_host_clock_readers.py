"""The per-layer readers of PR 36: JAX's compile pipeline, the collector's
pauses and a host clock for every report, which the program carries to the
driver under ``Result.metrics["_spans"]`` (span name -> ``count``,
``total_s``, ``max_s``, ``first_start``, ``last_end`` and ``recent``, the
``(start, end)`` of the name's last 256 spans).  The parent commit's
``_spans`` has no ``recent`` and no ``jax.*``: every reader gives ``None``."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAMES = ("compile.trace_s", "compile.lower_s", "compile.backend_s",
         "compile.cache_load_s", "compile.programs",
         "host.step_max_over_median", "host.gc_pause_ms")
MOVES = {"compile": ("compile cache", "setup_s"),
         "host": ("host loop", "train_tokens_per_s")}


def _module(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(count, total_s, max_s, start=100.0, recent=None):
    s = {"count": count, "total_s": total_s, "max_s": max_s,
         "first_start": start, "last_end": start + total_s}
    if recent is not None:
        s["recent"] = recent
    return s


def _reports(starts):
    return _span(len(starts), 2e-4 * len(starts), 2e-4, starts[0],
                 [(t, t + 2e-4) for t in starts])


def _run(spans, window_start=1000.0, elapsed_s=12.0):
    return {"worker": {"_spans": spans, "window_start": window_start,
                       "window": {"elapsed_s": elapsed_s}}}


# Two warm-up reports, a window of eleven with one 2.02 s interval among
# 0.82 s ones (0.82 + a stall of 1.2 s), then three traced steps' and the
# loop's last report.
WINDOW = [1000.82 + 0.82 * i for i in range(6)]
WINDOW += [WINDOW[-1] + 2.02 + 0.82 * i for i in range(5)]
OUTSIDE = [990.0, 995.0] + [1013.0 + 3.0 * i for i in range(3)] + [1030.0]
PAUSES = [(999.0, 999.4), (1003.0, 1003.05), (1011.9, 1013.0),
          (1020.0, 1020.3)]
WARM = {
    "session.report": _reports(sorted(WINDOW + OUTSIDE)),
    "jax.trace": _span(41, 6.5, 3.1),
    "jax.lower": _span(41, 9.25, 7.0),
    "jax.compile": _span(41, 11.5, 8.0),
    "jax.cache_load": _span(38, 10.0, 7.5),
    "gc.pause": _span(4, 1.85, 1.1, 999.0, PAUSES),
}


@pytest.mark.parametrize("name,value", [
    ("compile.trace_s", 6.5), ("compile.lower_s", 9.25),
    ("compile.backend_s", 11.5), ("compile.cache_load_s", 10.0),
    ("compile.programs", 41),
    ("host.step_max_over_median", 2.02 / 0.82),
    # the two pauses that START in the window; one ends after it
    ("host.gc_pause_ms", 1e3 * (0.05 + 1.1)),
])
def test_reader_on_a_warm_run(name, value):
    assert _module(name).read(_run(WARM)) == pytest.approx(value, rel=1e-9)


def test_a_stall_reads_2_46_and_names_its_report():
    mod = _module("host.step_max_over_median")
    assert round(mod.read(_run(WARM)), 2) == 2.46
    which, seconds = mod.stalled(_run(WARM))
    assert which == 6 and seconds == pytest.approx(2.02)
    even = dict(WARM, **{"session.report": _reports(
        [1000.82 + 0.82 * i for i in range(12)] + OUTSIDE[-1:])})
    assert mod.read(_run(even)) == pytest.approx(1.0)


def test_reports_outside_the_window_are_ignored():
    mod = _module("host.step_max_over_median")
    # Only the warm-up's, the traced steps' and the last report: 5 s and
    # 17 s apart, none of them the window's.
    run = _run({"session.report": _reports(OUTSIDE)})
    assert mod.read(run) is None and mod.stalled(run) is None
    # Three reports in the window make two intervals: too few.
    few = _reports(OUTSIDE[:2] + WINDOW[:3] + OUTSIDE[2:])
    assert mod.read(_run({"session.report": few})) is None
    four = _reports(OUTSIDE[:2] + WINDOW[:4] + OUTSIDE[2:])
    assert mod.read(_run({"session.report": four})) == pytest.approx(1.0)
    # The same reports, JSON's lists for tuples (``--details``).
    as_json = json.loads(json.dumps(_run(WARM)))
    assert round(mod.read(as_json), 2) == 2.46


def test_a_cold_run_and_a_window_without_pauses_read_zero():
    cold = {k: v for k, v in WARM.items() if k != "jax.cache_load"}
    assert _module("compile.cache_load_s").read(_run(cold)) == 0.0
    quiet = {k: v for k, v in WARM.items() if k != "gc.pause"}
    assert _module("host.gc_pause_ms").read(_run(quiet)) == 0.0
    early = dict(WARM, **{"gc.pause": _span(1, 0.4, 0.4, 999.0, PAUSES[:1])})
    assert _module("host.gc_pause_ms").read(_run(early)) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_read_nothing(name):
    read = _module(name).read
    parent = {  # PR 23's shape: totals only, the program's own names
        "train.fit": _span(1, 60.0, 60.0),
        "worker.spawn": _span(1, 0.65, 0.65),
        "train.loop": _span(1, 55.0, 55.0),
        "session.report": _span(20, 0.003, 0.0009),
    }
    assert read(_run(parent)) is None
    assert read({"worker": {"device": {}}}) is None  # no spans at all
    assert read({"worker": {"_spans": None}}) is None  # a failed session


def test_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    # Found by name: in this order, side by side, after everything the file
    # had then; what later PRs append comes after them.
    first = names.index(NAMES[0])
    assert names[first:first + len(NAMES)] == list(NAMES)
    assert all(names.count(n) == 1 for n in NAMES)
    ours = entries[first:first + len(NAMES)]
    older_layers = {m["layer"] for m in entries[:first]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in ours:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert "workloads" not in m  # every training cell, later ones too
        layer, moves = MOVES[m["name"].split(".")[0]]
        assert (m["layer"], m["moves"]) == (layer, moves), m["name"]
        assert m["layer"] in older_layers and m["moves"] in end_to_end
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
