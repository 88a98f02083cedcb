"""The reader PR 58 added, ``rope.kernel_ms``:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rope_kernel_ms.py
-q``.  Its cases count in tier-1 through ``tier1_cases.py`` (PR 62)."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "rope.kernel_ms"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(kernels, step_s=(0.35, 0.35)):
    return {"worker": {"trace": {"devices": [
        {"step_s": list(step_s), "steps": len(step_s), "kernels": kernels,
         "scopes": {}}]}}}


def test_the_entry_is_written_as_the_flash_times_are():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    flash, = [m for m in bench["per_layer"] if m["name"] == "flash.fwd_ms"]
    # Mellum2's cell rotates through the kernel too (and a traced run there
    # would read it), but ``test_mellum.py`` pins the metrics that list it
    cells = ["mistral7b-train-s4096", "mistral7b-train-s512",
             "olmoe-train-s4096"]
    assert metric == {**flash, "name": NAME, "workloads": cells}
    # found by its NAME (PR 62): PR 59's five entries stand behind it
    assert set(cells) <= {w["name"] for w in bench["workloads"]}


def test_the_sum_where_the_trace_has_the_kernels_and_none_where_not():
    named = {"flash_fwd": 26.5e-3, "flash_dkv": 36.6e-3, "flash_dq": 31.8e-3,
             "moe_gmm_swiglu": 9e-3, "moe_tgmm": 7e-3}
    # the XLA form (the parent): every Mosaic kernel of the cell is named ...
    assert _read(_run(named)) is None
    assert _read({"worker": {}}) is None
    # ... but the expert layer's buffer that writes nothing: no rotation
    assert _read(_run({**named, "unnamed": 1.375e-8})) is None
    # as today's reduction files them: it knows no ``rope_``
    unnamed = {"unnamed": 3.6e-3, "unnamed.remat": 1.8e-3, **named}
    assert abs(_read(_run(unnamed)) - 5.4) < 1e-9
    # and named by a reduction that knows the prefix
    known = {"rope_fwd": 1.8e-3, "rope_fwd.remat": 1.8e-3, "rope_bwd": 1.8e-3,
             "unnamed": 1.375e-8, **named}
    assert abs(_read(_run(known)) - 5.4) < 1e-4
