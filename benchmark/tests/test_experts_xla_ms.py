"""The reader PR 47 added, ``moe.experts_xla_ms``:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_experts_xla_ms.py
-q``.  Not part of tier-1."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "moe.experts_xla_ms"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(kernels, scopes, step_s=(0.33, 0.33)):
    return {"worker": {"trace": {"devices": [
        {"step_s": list(step_s), "steps": len(step_s), "kernels": kernels,
         "scopes": scopes}]}}}


def test_the_entry_lists_the_expert_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    shares, = [m for m in bench["per_layer"]
               if m["name"] == "moe.token_rows_read_share"]
    assert metric == {**shares, "name": NAME, "unit": "ms",
                      "source": "device_trace"}


def test_the_scope_less_its_kernels_whatever_their_names():
    flash = {"flash_fwd": 4e-3, "flash_dkv": 6e-3}
    scope = {"moe_experts": {"forward": 22e-3, "remat": 22e-3,
                             "backward": 52e-3},
             "moe_combine": {"forward": 11e-3}}
    # three products and XLA's passes between them
    parent = {"moe_gmm": 40e-3, "moe_gmm.remat": 20e-3, "moe_tgmm": 22e-3}
    assert abs(_read(_run({**parent, **flash}, scope)) - 14.0) < 1e-9
    # the one rule: every millisecond of the scope is a kernel's
    rule = {"moe_gmm_swiglu": 13e-3, "moe_gmm_swiglu.remat": 13e-3,
            "moe_gmm": 7e-3, "moe_gmm.remat": 7e-3, "moe_gmm_dswiglu": 7e-3,
            "moe_gmm_pair": 13e-3, "moe_tgmm": 22e-3}
    scope["moe_experts"] = {"forward": 20e-3, "remat": 20e-3,
                            "backward": 42e-3}
    assert abs(_read(_run({**rule, **flash}, scope))) < 1e-9
    # a dense model's step, an untraced run
    assert _read(_run(flash, {"ffn": {"forward": 0.1}})) is None
    assert _read({"worker": {}}) is None
