"""The yardstick of the OLMoE cell: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_olmoe.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import pytest

from benchmark import flops, flops_moe, trace_reduce
from benchmark.loops import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "olmoe-1b-7b-0125-1chip"


def _conf():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_published_widths_against_the_catalog_row():
    """Every key of the catalog row's ``config`` (model-configs guide,
    ``architectures.jsonl``, OLMoE-1B-7B-0125-Instruct: the copy under
    ``testdata/published``) is in the file unchanged; only depth is reduced."""
    with open(os.path.join(BENCH, "testdata", "published",
                           NAME + ".json")) as f:
        published = json.load(f)
    assert (published["num_hidden_layers"], len(published)) == (16, 18)
    conf = _conf()
    differ = [k for k, v in published.items() if conf[k] != v]
    assert differ == list(conf["reduced"]) == ["num_hidden_layers"]
    assert conf["reduced"]["num_hidden_layers"] == {
        "published": 16, "run": conf["num_hidden_layers"],
        "why": conf["reduced"]["num_hidden_layers"]["why"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == conf["source"]
    # what the program is given, through the file's own map
    cfg = train.program_config(conf)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size) == (2048, 16, 16, 128, 1024, 50304)
    assert (cfg.num_experts, cfg.num_selected, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.norm_eps, cfg.aux_loss_coef, cfg.z_loss_coef
            ) == (64, 8, False, True, 1e-5, 0.01, 0.001)
    for key in ("qk_norm", "router_aux_loss_coef", "router_z_loss_coef"):
        assert conf[key] == conf["assumed"][key]["value"]


def test_flops_moe_against_hand_counts():
    """ISSUE 25's arithmetic from the catalog row."""
    conf = dict(_conf(), num_hidden_layers=16)
    attention, experts, router = 4 * 2048 ** 2, 8 * 3 * 2048 * 1024, 2048 * 64
    assert (attention, experts, router) == (16777216, 50331648, 131072)
    head = 2048 * 50304
    assert flops_moe.active_matmul_params(conf) == 16 * (
        attention + experts + router) + head
    one_layer = (4 * 2048 ** 2 + 64 * 3 * 2048 * 1024 + 2048 * 64
                 + 4 * 2048)  # and the four norms
    assert one_layer == 419569664  # the issue's 419.6 M
    assert flops_moe.total_params(conf) == 16 * one_layer + 2 * head + 2048
    causal = 6 * 4096 * 16 * 128
    assert flops_moe.train_flops_per_token(conf, 4096) == 16 * (
        6 * (attention + experts + router) + causal) + 6 * head
    cut = dict(conf, num_hidden_layers=2)
    per_token = flops_moe.train_flops_per_token(cut, 4096)
    assert per_token == pytest.approx(1.526e9, rel=1e-3)
    assert 2 * 6 * experts / per_token == pytest.approx(0.396, abs=1e-3)
    assert 6 * head / per_token == pytest.approx(0.405, abs=1e-3)
    assert 6 * head / flops_moe.train_flops_per_token(conf, 4096) \
        == pytest.approx(0.078, abs=1e-3)
    # the grouped products of a 4 x 4096 step: 131072 rows a layer
    rows = 4 * 4096 * 8
    assert flops_moe.experts_step_flops(cut, 4, 4096) == \
        2 * 3 * 3 * 2 * rows * 2048 * 1024
    assert flops_moe.experts_step_bytes(cut, 4, 4096) == 2 * (
        9 * rows * (2048 + 1024) * 2 + 3 * 64 * 3 * 2048 * 1024 * 2)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"conf": cut, "job": {"rows": 4, "seq": 4096}, "peak": peak}
    assert _reader("moe.experts_roofline").bound(run) == "compute"


OP = ('%{name} = bf16[4096,2048]{{1,0:T(8,128)(2,1)}} {opcode}(%p.1), '
      '{rest}metadata={{op_name="x"}}')
MOSAIC = 'custom_call_target="tpu_custom_call", '


def _planes():
    """Two steps after a lead-in; per step a flash kernel (100 ns), two
    grouped products (300 ns forward, 200 ns of the weights' gradient), a
    router fusion (50), a scan op that covers a gather (40 of its 60), an
    optimizer fusion (80) and an op with no scope (20)."""
    texts = {
        "flash": OP.format(name="closed_call.1", opcode="custom-call",
                           rest=MOSAIC),
        "gmm": OP.format(name="custom-call.7", opcode="custom-call",
                         rest=MOSAIC),
        "tgmm": OP.format(name="custom-call.9", opcode="custom-call",
                          rest=MOSAIC),
        "route": OP.format(name="fusion.1", opcode="fusion", rest=""),
        "scan": OP.format(name="while.2", opcode="while", rest=""),
        "gather": OP.format(name="fusion.5", opcode="fusion", rest=""),
        "adam": OP.format(name="fusion.8", opcode="fusion", rest=""),
        "copy": OP.format(name="copy.3", opcode="copy", rest=""),
    }
    stacks = {
        "flash": "jit(step)/jvp(attention)/flash_fwd",
        "gmm": "jit(step)/rematted_computation/moe_experts/moe_gmm",
        "tgmm": "jit(step)/transpose(jvp(moe_experts))/moe_tgmm",
        "route": "jit(step)/jvp(moe_route)/dot_general",
        "scan": "jit(step)/while/body",
        "gather": "jit(step)/while/body/jvp(moe_dispatch)/gather",
        "adam": "jit(step)/optimizer/mul",
        "copy": "jit(step)/copy",
    }
    ops, mods = [], []
    for i, start in enumerate((0, 1000, 2000)):
        mods.append((f"jit_step({i})", start, start + 900))
        ops += [(texts["flash"], start, start + 100),
                (texts["gmm"], start + 100, start + 400),
                (texts["tgmm"], start + 400, start + 600),
                (texts["route"], start + 600, start + 650),
                (texts["scan"], start + 650, start + 710),
                (texts["gather"], start + 660, start + 700),
                (texts["adam"], start + 710, start + 790),
                (texts["copy"], start + 790, start + 810)]
    planes = {"/device:TPU:0": {"XLA Ops": sorted(ops, key=lambda e: e[1]),
                                "XLA Modules": mods},
              "/host:CPU": {"python": []}}
    names = {"/device:TPU:0": {texts[k]: stacks[k] for k in texts}}
    return planes, names


def test_scopes_kernels_and_readers_on_synthetic_planes():
    from benchmark import trace_scopes

    assert trace_scopes.scope_and_phase(
        "jit(step)/transpose(jvp(moe_experts))/moe_tgmm") == (
            "moe_experts", "backward")
    assert trace_scopes.scope_and_phase(
        "jit(step)/rematted_computation/moe_route/top_k") == (
            "moe_route", "remat")
    assert trace_scopes.scope_and_phase("jit(step)/while/body/add") == (
        "scan", "forward")
    assert trace_scopes.kernel_name("a/moe_experts/moe_gmm") == "moe_gmm"
    assert trace_scopes.kernel_name("a/moe_experts/dot") == "unnamed"

    planes, names = _planes()
    trace = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                       annotations=(), names=names)
    d, = trace["devices"]
    ns = 1e-9
    assert d["steps"] == 2
    assert d["scopes"]["moe_experts"] == {
        "remat": pytest.approx(300 * ns), "backward": pytest.approx(200 * ns)}
    assert d["scopes"]["moe_route"] == {"forward": pytest.approx(50 * ns)}
    assert d["scopes"]["moe_dispatch"] == {"forward": pytest.approx(40 * ns)}
    assert d["scopes"]["scan"] == {"forward": pytest.approx(20 * ns)}
    assert d["scopes"]["optimizer"] == {"optimizer": pytest.approx(80 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    assert d["kernels"] == {"flash_fwd": pytest.approx(100 * ns),
                            "moe_gmm.remat": pytest.approx(300 * ns),
                            "moe_tgmm": pytest.approx(200 * ns)}
    # every Mosaic call is a kernel; flash is those named flash_* alone
    assert d["kernels_s"] == pytest.approx(2 * 600 * ns)
    assert d["flash_s"] == pytest.approx(2 * 100 * ns)
    assert d["device_ops"][0] == [
        "moe_experts/remat custom-call.7 custom-call bf16[4096,2048]",
        pytest.approx(2 * 300 * ns)]

    conf = dict(_conf())
    assert flops.of(conf) is flops_moe
    run = {"worker": {"trace": trace, "window": {"step_metrics": {
               "moe_dropped": 0.0, "moe_load_max_over_mean": 1.25}}},
           "conf": conf, "job": {"rows": 4, "seq": 4096}, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "end_to_end": {"train_tokens_per_s": 50000.0}}
    step_s = sum(d["step_s"]) / 2
    assert step_s == pytest.approx(900 * ns)
    assert _reader("moe.time_share_pct").read(run) == pytest.approx(
        100 * 590 * ns / step_s)
    assert _reader("moe.dispatch_ms").read(run) == pytest.approx(90e-6)
    assert _reader("moe.experts_roofline").read(run) == pytest.approx(
        100 * flops_moe.experts_step_flops(conf, 4, 4096) / 197e12
        / (500 * ns))
    assert _reader("flash.time_share_pct").read(run) == pytest.approx(
        100 * 100 * ns / step_s)
    assert _reader("moe.load_max_over_mean").read(run) == 1.25
    # the one MFU reader counts with the module the configuration names:
    # eight experts and the router a token, not flops.py's one FFN
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 50000.0 * flops_moe.train_flops_per_token(conf, 4096) / 197e12)
    assert _reader("train_step.mfu_pct").read(run) > 1.5 * _reader(
        "train_step.mfu_pct").read(dict(run, conf=dict(conf, flops="flops")))
    # the step by scope: the shares, and the kernels' milliseconds
    shares = {m: _reader("step." + m + "_pct").read(run) for m in (
        "ffn", "attn_proj", "attention", "head_loss", "optimizer", "remat",
        "scan", "unscoped")}
    assert shares == {
        "ffn": 0.0, "attn_proj": None, "head_loss": None,
        "attention": pytest.approx(100 * 100 / 900),
        "optimizer": pytest.approx(100 * 80 / 900),
        "remat": pytest.approx(100 * 300 / 900),
        "scan": pytest.approx(100 * 20 / 900),
        "unscoped": pytest.approx(100 * 20 / 900)}
    assert sum(v for m, v in shares.items() if v and m != "remat") \
        + _reader("moe.time_share_pct").read(run) == pytest.approx(
            100 * 810 / 900)  # the trace's ops; 90 of the 900 ns are idle
    assert _reader("flash.fwd_ms").read(run) == pytest.approx(100e-6)
    assert _reader("flash.dkv_ms").read(run) is None
    assert _reader("flash.dq_ms").read(run) is None

    # a trace without name stacks (a program from before the scopes), and
    # a run without a trace: nothing to read, nothing raised
    bare = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                      annotations=())
    old = dict(run, worker={"trace": bare, "window": {}})
    untraced = dict(old, worker={"trace": None, "window": {}})
    for metric in ("moe.experts_roofline", "moe.load_max_over_mean",
                   "step.attention_pct", "step.scan_pct", "step.remat_pct",
                   "flash.fwd_ms"):
        assert _reader(metric).read(old) is None, metric
        assert _reader(metric).read(untraced) is None
    # ``step.ffn_pct``, ``moe.time_share_pct`` and ``moe.dispatch_ms`` carry
    # no list of cells: nothing under the scope in a traced step (``ffn`` in
    # the MoE run above, ``moe_*`` in a dense model's step) is the share 0,
    # no step is no share.  An expert model's step with nothing under
    # ``moe_*`` (its name stacks lost) is a fault: no number, not a 0
    assert _reader("step.ffn_pct").read(run) == 0.0
    assert _reader("step.ffn_pct").read(old) == 0.0
    dense = dict(old, conf=dict(conf, flops="flops"))
    for metric in ("moe.time_share_pct", "moe.dispatch_ms"):
        assert _reader(metric).read(dense) == 0.0, metric
        assert _reader(metric).read(old) is None, metric
    for metric in ("step.ffn_pct", "moe.time_share_pct", "moe.dispatch_ms"):
        assert _reader(metric).read(untraced) is None, metric
        assert _reader(metric).read(dict(dense, worker=untraced["worker"])
                                    ) is None, metric
    assert _reader("step.unscoped_pct").read(old) == pytest.approx(
        100 * 810 / 900)
