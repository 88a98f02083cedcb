"""The reader PR 41 added, ``moe.token_rows_read_share``:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_rows_read.py
-q``.  Not part of tier-1."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "moe.token_rows_read_share"
EXPERT_CELLS = {"olmoe-train-s4096", "xing4-train-s8192",
                "lfm2moe-train-s8192"}


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def test_the_entry_stands_with_the_three_expert_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    cells = metric.pop("workloads")
    assert metric == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "experts",
        "moves": "train_tokens_per_s"}
    # found by name: a later PR that appends an expert cell passes
    assert EXPERT_CELLS <= set(cells) <= {w["name"]
                                          for w in bench["workloads"]}


def test_reads_the_checks_program_parts_and_nothing_from_a_parent():
    parts = {"loss": 13.3, "moe_dropped": 0.0,
             "moe_rows_visited_share": 0.1602,
             "moe_token_rows_read_share": 0.40625}
    assert _read({"worker": {"check": {"program_parts": parts}}}) == 0.40625
    # a program from before the counter, and a cell without experts
    parts.pop("moe_token_rows_read_share")
    assert _read({"worker": {"check": {"program_parts": parts}}}) is None
    assert _read({"worker": {"check": {}}}) is None
    assert _read({"worker": {}}) is None
