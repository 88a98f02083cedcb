"""The per-layer readers of PR 50: what set-up is made of.  The program
opens its chips under ``device.bring_up`` > ``jax.import``,
``jax.backend_init`` before the loop, and ``setup.unattributed_s`` closes
``setup_s`` by a remainder: what no span of ``Result.metrics["_spans"]``
covers between the process start and the window's.  The parent commit's
``_spans`` has no ``device.bring_up``: every reader gives ``None``."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAMES = ("setup.device_bringup_s", "setup.jax_import_s",
         "setup.unattributed_s")


def _module(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(*recent, count=None, first_start=None):
    """A ``_spans`` entry of these ``(start, end)`` intervals."""
    recent = sorted(recent)
    lengths = [end - start for start, end in recent]
    return {"count": len(recent) if count is None else count,
            "total_s": sum(lengths), "max_s": max(lengths),
            "first_start": recent[0][0] if first_start is None
            else first_start,
            "last_end": max(end for _, end in recent), "recent": recent}


def _run(spans, process_start=1000.0, window_start=1040.0):
    return {"process_start": process_start,
            "worker": {"_spans": spans, "window_start": window_start}}


# A set-up of 40 s.  Process start 1000, fit() at 1001, the worker's
# session at 1002.5, the bring-up 1003-1013.5 (import 2.5 s, runtime 8 s),
# JAX's pipeline in three programs with a cache load inside a compile,
# two warm-up reports, the window from 1040; later spans lie in it.
SPANS = {
    "train.fit": _span((1001.0, 1075.0)),
    "train.run": _span((1002.2, 1074.0)),
    "train.loop": _span((1013.5, 1073.9)),
    "train.placement_group": _span((1001.0, 1001.1)),
    "train.start_workers": _span((1001.1, 1002.2)),
    "sched.wait": _span((1001.15, 1001.2)),          # inside start_workers
    "worker.spawn": _span((1001.2, 1002.0)),         # inside start_workers
    "train.backend_start": _span((1002.2, 1002.2)),
    "train.session_start": _span((1002.5, 1003.0)),
    "device.bring_up": _span((1003.0, 1013.5)),
    "jax.import": _span((1003.0, 1005.5)),           # inside the bring-up
    "jax.backend_init": _span((1005.5, 1013.5)),     # inside the bring-up
    "jax.trace": _span((1016.0, 1018.0), (1022.0, 1030.0), (1033.0, 1033.5)),
    "jax.lower": _span((1018.0, 1019.0), (1030.0, 1032.0)),
    "jax.compile": _span((1019.0, 1020.0), (1032.0, 1033.0)),
    "jax.cache_load": _span((1019.1, 1019.9), (1032.1, 1032.9)),
    "jax.cache_miss": _span((1019.0, 1019.0)),
    "gc.pause": _span((1017.0, 1017.2), (1021.0, 1021.5), (1050.0, 1050.1)),
    # the last warm-up report straddles the window's start
    "session.report": _span((1036.0, 1036.1), (1039.9, 1040.2),
                            (1041.0, 1041.1), (1042.0, 1042.1)),
    "train.shutdown": _span((1074.0, 1075.0)),
}
# Counted once: 1001-1002.2 driver, 1002.5-1013.5 session + bring-up,
# 1016-1020 and 1022-1033.5 JAX (the pause at 1021 between them: +0.5),
# 1036-1036.1 and 1039.9-1040 (clipped) reports.
UNION = 1.2 + 11.0 + 4.0 + 0.5 + 11.5 + 0.1 + 0.1


def test_the_bring_up_and_its_import():
    run = _run(SPANS)
    assert _module("setup.device_bringup_s").read(run) == 10.5
    assert _module("setup.jax_import_s").read(run) == 2.5


def test_the_union_counts_nested_and_overlapping_spans_once():
    mod = _module("setup.unattributed_s")
    union, setup_s = mod.covered(_run(SPANS))
    assert setup_s == 40.0
    assert union == pytest.approx(UNION, abs=1e-9)
    assert mod.read(_run(SPANS)) == pytest.approx(40.0 - UNION, abs=1e-9)


def test_the_books_close_to_the_millisecond():
    mod = _module("setup.unattributed_s")
    for start, window in ((1000.0, 1040.0), (999.123456, 1037.654321),
                          (1000.9, 1019.5)):
        run = _run(SPANS, start, window)
        union, setup_s = mod.covered(run)
        assert setup_s == window - start
        assert abs(union + mod.read(run) - setup_s) < 1e-3
        assert 0.0 <= mod.read(run) <= setup_s


def test_intervals_are_clipped_at_both_ends_of_set_up():
    mod = _module("setup.unattributed_s")
    spans = {"device.bring_up": _span((1003.0, 1013.5)),
             "probe.early": _span((990.0, 1001.0)),    # began before start
             "probe.late": _span((1039.0, 1060.0)),    # ends in the window
             "probe.after": _span((1045.0, 1046.0))}   # all in the window
    union, setup_s = mod.covered(_run(spans))
    assert (union, setup_s) == (1.0 + 10.5 + 1.0, 40.0)


def test_containers_are_ignored():
    mod = _module("setup.unattributed_s")
    assert mod.CONTAINERS == ("train.fit", "train.run", "train.loop")
    spans = {name: SPANS[name] for name in mod.CONTAINERS}
    spans["device.bring_up"] = SPANS["device.bring_up"]
    assert mod.read(_run(spans)) == 40.0 - 10.5


def test_json_lists_read_as_tuples():
    """``--details`` and the wire carry ``recent`` as lists of lists."""
    mod = _module("setup.unattributed_s")
    run = json.loads(json.dumps(_run(SPANS)))
    assert mod.read(run) == pytest.approx(40.0 - UNION, abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_read_nothing(name):
    read = _module(name).read
    parent = {k: v for k, v in SPANS.items()
              if k not in ("device.bring_up", "jax.import",
                           "jax.backend_init")}
    assert read(_run(parent)) is None
    assert read(_run(None)) is None  # a failed session
    assert read({"process_start": 1000.0,
                 "worker": {"window_start": 1040.0}}) is None


def test_lost_intervals_give_no_number():
    """A name with more spans than ``recent`` keeps, whose oldest kept one
    is later than its first: part of set-up's cover is gone."""
    mod = _module("setup.unattributed_s")
    lost = dict(SPANS, **{"jax.trace": _span(
        (1022.0, 1030.0), (1033.0, 1033.5), count=300, first_start=1016.0)})
    assert mod.read(_run(lost)) is None
    # ... but not where the name began in the window: nothing of set-up's
    late = dict(SPANS, **{"session.report": _span(
        (1050.0, 1050.1), count=300, first_start=1040.5)})
    assert mod.read(_run(late)) == pytest.approx(
        40.0 - UNION + 0.2, abs=1e-9)
    # ... nor where every interval is still there (count says so)
    assert mod.read(_run(SPANS)) is not None
    # a name that kept no interval at all has lost them
    none = dict(SPANS, **{"probe.old": {
        "count": 2, "total_s": 1.0, "max_s": 0.5, "first_start": 1014.0,
        "last_end": 1015.0}})
    assert mod.read(_run(none)) is None


def test_the_bring_up_alone_is_the_unattributed_readers_condition():
    mod = _module("setup.unattributed_s")
    assert mod.read(_run({"device.bring_up": SPANS["device.bring_up"]})) \
        == 40.0 - 10.5


def test_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "entry and spawn",
            "moves": "setup_s"}, name  # no ``workloads``: every cell
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".py"))
