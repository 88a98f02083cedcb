"""The reader PR 39 added, ``moe.rows_visited_share``: ``JAX_PLATFORMS=cpu
python -m pytest benchmark/tests/test_rows_visited.py -q``.  Not part of
tier-1."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "moe.rows_visited_share"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def test_the_entry_lists_both_expert_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    cells = metric.pop("workloads")
    assert metric == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "experts",
        "moves": "train_tokens_per_s"}
    # the two expert cells of PR 39 lead the list, by name; every cell a
    # later PR appended is a cell of the benchmark
    assert cells[:2] == ["olmoe-train-s4096", "xing4-train-s8192"]
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    assert len(set(cells)) == len(cells)


def test_reads_the_checks_program_parts_and_nothing_from_a_parent():
    parts = {"loss": 13.3, "moe_dropped": 0.0,
             "moe_rows_visited_share": 0.1602}
    assert _read({"worker": {"check": {"program_parts": parts}}}) == 0.1602
    # a program from before the counter, and a cell without experts
    parts.pop("moe_rows_visited_share")
    assert _read({"worker": {"check": {"program_parts": parts}}}) is None
    assert _read({"worker": {"check": {}}}) is None
    assert _read({"worker": {}}) is None
