"""The reader PR 42 added, ``gdn.kernel_ms``:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_gdn_kernel_ms.py
-q``.  Not part of tier-1."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "gdn.kernel_ms"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(kernels, step_s=(0.33, 0.33)):
    return {"worker": {"trace": {"devices": [
        {"step_s": list(step_s), "steps": len(step_s), "kernels": kernels,
         "scopes": {}}]}}}


def test_the_entry_is_written_as_the_scan_times_is():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    scan, = [m for m in bench["per_layer"] if m["name"] == "gdn.scan_ms"]
    assert metric == {**scan, "name": NAME}
    assert metric["workloads"] == ["olmohybrid-train-1seq"]


def test_the_sum_where_the_trace_has_the_kernels_and_none_where_not():
    flash = {"flash_fwd": 1.04e-3, "flash_dkv": 1.52e-3, "flash_dq": 1.25e-3}
    # the XLA form (the parent): the cell's only Mosaic kernels are flash's
    assert _read(_run(flash)) is None
    assert _read({"worker": {}}) is None
    # named by a reduction that knows the prefix ...
    named = {"delta_fwd": 9e-3, "delta_fwd.remat": 9.5e-3,
             "delta_bwd": 5e-3, **flash}
    assert abs(_read(_run(named)) - 23.5) < 1e-9
    # ... and as today's reduction files them: it knows no ``delta_``
    unnamed = {"unnamed": 14e-3, "unnamed.remat": 9.5e-3, **flash}
    assert abs(_read(_run(unnamed)) - 23.5) < 1e-9
