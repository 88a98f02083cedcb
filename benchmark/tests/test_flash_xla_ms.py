"""The reader PR 54 added, ``flash.xla_ms``:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_flash_xla_ms.py
-q``.  Its cases count in tier-1 through ``tests/test_yardstick.py``."""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "flash.xla_ms"


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(kernels, scopes, step_s=(0.5, 0.5)):
    return {"worker": {"trace": {"devices": [
        {"step_s": list(step_s), "steps": len(step_s), "kernels": kernels,
         "scopes": scopes}]}}}


def test_the_entry_is_the_kernels_own_in_every_cell():
    """Beside ``flash.fwd_ms``: the same layer, the same end-to-end metric,
    no list of cells (every cell's step has the scope); found by its NAME
    (PR 62: later PRs' entries stand behind it)."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    fwd, = [m for m in bench["per_layer"] if m["name"] == "flash.fwd_ms"]
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {**fwd, "name": NAME}
    assert "workloads" not in fwd


def test_the_scope_less_the_flash_kernels_whatever_their_names():
    # Trinity's cell at PR 53: one full layer's kernels, four windowed
    # layers', the rematerialised forward's, and 16.6 ms that are XLA's
    scope = {"attention": {"forward": 18e-3, "remat": 2e-3,
                           "backward": 95e-3},
             "attn_qkv": {"forward": 22e-3, "remat": 34e-3,
                          "backward": 51e-3}}
    kernels = {"flash_fwd": 7e-3, "flash_fwd_win": 20e-3,
               "flash_dkv": 10e-3, "flash_dkv_win": 29e-3,
               "flash_dq": 8e-3, "flash_dq_win": 24e-3,
               "flash_fwd_win.remat": 0.4e-3}
    others = {"moe_gmm": 40e-3, "moe_tgmm": 22e-3, "ssd_fwd": 9e-3}
    assert abs(_read(_run({**kernels, **others}, scope)) - 16.6) < 1e-9
    # the other scopes' time and the other kernels' are not read
    scope["attn_qkv"]["remat"] = 25e-3
    assert abs(_read(_run(kernels, scope)) - 16.6) < 1e-9


def test_nothing_left_reads_zero_and_no_scope_reads_none():
    kernels = {"flash_fwd": 4e-3, "flash_dkv": 6e-3, "flash_dq": 5e-3}
    scope = {"attention": {"forward": 4e-3, "backward": 11e-3}}
    assert _read(_run(kernels, scope)) == 0.0
    # the sums' rounding may leave the scope a hair under its kernels
    scope["attention"]["backward"] = 11e-3 - 1e-12
    assert _read(_run(kernels, scope)) == 0.0
    # a model without an attention layer; an untraced run
    assert _read(_run({"ssd_fwd": 9e-3}, {"ssm_scan": {"forward": 9e-3}})) \
        is None
    assert _read({"worker": {}}) is None
    assert _read({"worker": {"trace": None}}) is None


def test_on_a_mesh_the_slowest_chip_is_read():
    fast = {"step_s": [0.4, 0.4], "steps": 2,
            "kernels": {"flash_fwd": 5e-3},
            "scopes": {"attention": {"forward": 6e-3}}}
    slow = {"step_s": [0.5, 0.5], "steps": 2,
            "kernels": {"flash_fwd": 5e-3},
            "scopes": {"attention": {"forward": 9e-3}}}
    run = {"worker": {"trace": {"devices": [fast, slow]}}}
    assert abs(_read(run) - 4.0) < 1e-9
