"""The per-layer readers of PR 23: numbers the program carries to the
driver itself, under ``Result.metrics["_spans"]`` (span name -> ``count``,
``total_s``, ``max_s``, ``first_start``, ``last_end``).  A program without
spans — the parent commit — gives no ``_spans`` and every reader ``None``."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(count, total_s, max_s, start=100.0):
    return {"count": count, "total_s": total_s, "max_s": max_s,
            "first_start": start, "last_end": start + total_s}


SPANS = {
    "sched.wait": _span(1, 0.004, 0.004),
    "worker.spawn": _span(1, 0.61, 0.61),
    "train.backend_start": _span(1, 0.0002, 0.0002),
    "session.report": _span(20, 0.003, 0.0009),
}

CASES = [
    ("spawn.sched_wait_s", 0.004),
    ("spawn.worker_boot_s", 0.61),
    ("spawn.backend_start_s", 0.0002),
    ("session.report_us", 150.0),
]


@pytest.mark.parametrize("name,value", CASES)
def test_reader_with_spans(name, value):
    run = {"worker": {"device": {}, "_spans": SPANS}}
    assert _reader(name)(run) == pytest.approx(value)


@pytest.mark.parametrize("name,value", CASES)
def test_reader_without_spans_reads_nothing(name, value):
    read = _reader(name)
    assert read({"worker": {"device": {}}}) is None  # the parent commit
    assert read({"worker": {"_spans": None}}) is None  # a failed session
    assert read({"worker": {"_spans": {"train.fit": _span(1, 9, 9)}}}) is None


def test_slowest_worker_of_a_gang_is_read():
    run = {"worker": {"_spans": {"worker.spawn": _span(4, 2.0, 0.8),
                                 "sched.wait": _span(4, 1.0, 0.7)}}}
    assert _reader("spawn.worker_boot_s")(run) == 0.8
    assert _reader("spawn.sched_wait_s")(run) == 0.7


def test_entries_in_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name, _ in CASES:
        m = by_name[name]
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert "workloads" not in m  # every cell reports it
        assert m["layer"] in layers
    assert by_name["session.report_us"]["moves"] == "train_tokens_per_s"
    assert by_name["spawn.worker_boot_s"]["moves"] == "setup_s"
