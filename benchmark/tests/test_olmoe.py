"""The yardstick of the OLMoE cell: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_olmoe.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import pytest

from benchmark import flops_moe, trace_scopes
from benchmark.loops import train_moe

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
    ``architectures.jsonl``, OLMoE-1B-7B-0125-Instruct, as written in
    ISSUE 25) is in the file unchanged; only depth is reduced."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
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
    cfg = train_moe.program_config(conf)
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


def test_trace_scopes_and_the_flash_correction_on_synthetic_planes():
    from benchmark import trace_reduce

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
    scoped = trace_scopes.reduce_planes(planes, names,
                                        step_module="jit_step")
    d = scoped[0]
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
    assert d["flash_s"] == pytest.approx(100 * ns)

    # trace_reduce counts every Mosaic call as flash; the loop corrects it
    trace = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                       annotations=())
    assert trace["devices"][0]["flash_s"] == pytest.approx(2 * 600 * ns)
    trace = train_moe.by_scope(trace, scoped)
    dev, = trace["devices"]
    assert dev["flash_s"] == pytest.approx(2 * 100 * ns)
    assert dev["all_kernels_s"] == pytest.approx(2 * 600 * ns)

    conf = dict(_conf())
    run = {"worker": {"trace": trace, "window": {
               "moe_load_max_over_mean": [1.1, 1.25, 1.2]}},
           "conf": conf, "job": {"rows": 4, "seq": 4096}, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "end_to_end": {"train_tokens_per_s": 50000.0}}
    step_s = sum(dev["step_s"]) / 2
    assert _reader("moe.time_share_pct").read(run) == pytest.approx(
        100 * 590 * ns / step_s)
    assert _reader("moe.dispatch_ms").read(run) == pytest.approx(90e-6)
    assert _reader("moe.experts_roofline").read(run) == pytest.approx(
        100 * flops_moe.experts_step_flops(conf, 4, 4096) / 197e12
        / (500 * ns))
    assert _reader("flash.time_share_pct").read(run) == pytest.approx(
        100 * 100 * ns / step_s)
    assert _reader("moe.load_max_over_mean").read(run) == 1.25
    assert _reader("train_step.moe_mfu_pct").read(run) == pytest.approx(
        100 * 50000.0 * flops_moe.train_flops_per_token(conf, 4096) / 197e12)

    # a program from before the scopes: nothing to read, nothing raised
    bare = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                      annotations=())
    old = {"worker": {"trace": bare, "window": {}}, "conf": {"a": 1},
           "job": run["job"], "chips": 1, "peak": run["peak"],
           "end_to_end": run["end_to_end"]}
    for metric in ("moe.time_share_pct", "moe.dispatch_ms",
                   "moe.experts_roofline", "moe.load_max_over_mean",
                   "train_step.moe_mfu_pct"):
        assert _reader(metric).read(old) is None, metric
        assert _reader(metric).read(
            dict(old, worker={"trace": None, "window": {}})) is None


def _rehearsal_loop(config):
    """Test-only entry: the loop without the chip requirement."""
    import time

    import jax

    from benchmark.loops import train_moe
    from ray_tpu.air import session

    session.report(train_moe.measure(config, jax.devices(),
                                     {"loop_start": time.time()}))


def test_train_moe_loop_rehearsal_on_cpu_worker():
    """The whole loop at a tiny config through JaxTrainer.fit() with a
    CPU worker.  Asserts the shape of what comes back, no speed."""
    import ray_tpu as ray
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    job = {"loop": "train_moe", "rows": 4, "seq": 64, "mesh": None,
           "check_rows": 2, "warmup_steps": 2, "traced_steps": 2}
    conf = dict(_conf(), hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=32, vocab_size=256,
                num_experts=8, num_experts_per_tok=3, num_hidden_layers=2)
    ray.init(num_cpus=4, num_tpus=0)
    try:
        result = JaxTrainer(
            _rehearsal_loop,
            train_loop_config={"conf": conf, "job": job, "chips": 0,
                               "peaks": {}, "seed": 2147483653,
                               "seconds": 1.0, "trace": True,
                               "trace_dir": None},
            scaling_config=ScalingConfig(num_workers=1,
                                         tpu_chips_per_worker=0)).fit()
    finally:
        ray.shutdown()
    assert result.error is None, result.error
    w = result.metrics
    win = w["window"]
    assert win["attempted"] == win["steps"] >= 1 and win["failed"] == 0
    assert win["tokens"] == win["steps"] * 4 * 64
    assert win["compiles"] == 0 and win["error"] is None
    assert win["moe_dropped"] == 0
    assert len(win["moe_load_max_over_mean"]) == win["steps"]
    assert all(1.0 <= x <= 8.0 for x in win["moe_load_max_over_mean"])
    assert w["trace"] is None  # a CPU trace has no device plane to read
    assert len(result.metrics_history) == 2 + win["steps"] + 3 + 1
    # bfloat16 against the float32 reference at a tiny size: the total
    # loss and each of its three parts
    check = w["check"]
    assert abs(check["program_loss"] - check["reference_loss"]) \
        < 2e-2 * check["reference_loss"]
    for part in ("loss", "aux_loss", "z_loss"):
        assert check["program_parts"][part] == pytest.approx(
            check["reference_parts"][part], rel=3e-2), part
    assert check["program_parts"]["moe_dropped"] == 0
    run = {"worker": w, "process_start": w["loop_start"] - 1.0}
    assert train_moe.end_to_end(run)["train_tokens_per_s"] > 0
    # correct(): the dense loop's conditions (a chip reports its memory;
    # bfloat16 at this size is outside the chip check's tolerance), and
    # no dropped assignment
    good = dict(w, peak_bytes_in_use=[1], check=dict(
        check, program_loss=check["reference_loss"]))
    assert train_moe.correct({"worker": good}) is True
    assert train_moe.correct({"worker": dict(good, window=dict(
        win, moe_dropped=1.0))}) is False
    assert train_moe.correct({"worker": dict(good, check=dict(
        check, program_loss=1.001 * check["reference_loss"]))}) is False
