"""Checks of the yardstick itself, on the CPU.  Nothing here is a device
number: the rehearsal asserts counts and shapes of the result only."""

import json
import os
import statistics
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

from benchmark import flops, trace_reduce  # noqa: E402


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# A configuration file in the public key names, at CPU size.
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "vocab_size": 256,
    "assumed": {"param_dtype": {"value": "float32"},
                "dtype": {"value": "float32"}},
    "llama_config": _conf("mistral-7b-v0.1-d4")["llama_config"],
}


# ------------------------------------------------------------- flops.py --

@pytest.mark.parametrize("name,seq,matmul,total,attention", [
    # by hand: layers x (q + k + v + o + 3 FFN) + head; + embedding + norms
    ("mistral-7b-v0.1-d4", 4096,
     4 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336)
     + 4096 * 32000,
     1_134_596_096, 6 * 4 * 4096 * 32 * 128),
    ("deepseek-llm-7b-d20-x4", 4096,
     20 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400,
     4_886_532_096, 6 * 20 * 4096 * 32 * 128),
])
def test_flops_against_hand_counts(name, seq, matmul, total, attention):
    conf = _conf(name)
    assert flops.matmul_params(conf) == matmul
    assert flops.total_params(conf) == total
    assert flops.attention_flops_per_token(conf, seq) == attention
    assert flops.train_flops_per_token(conf, seq) == 6 * matmul + attention


def test_flash_roofline_names_its_bound():
    conf, peak = _conf("mistral-7b-v0.1-d4"), {"bf16_flops_per_s": 197e12,
                                               "hbm_bytes_per_s": 819e9}
    # one layer-row at s=4096: 6 s^2 h d FLOPs against 12 tensors' bytes
    f = flops.flash_step_flops(conf, 1, 4096)
    b = flops.flash_step_bytes(conf, 1, 4096)
    assert f == 4 * 6 * 4096 ** 2 * 32 * 128
    assert b == 4 * 6 * 4096 * (32 + 8) * 128 * 2
    assert flops.roofline_seconds(f, b, peak)["bound"] == "compute"
    # with 8 KV heads, s=512 is 204.8 FLOP/byte, under the chip's 240.5
    short = flops.roofline_seconds(flops.flash_step_flops(conf, 32, 512),
                                   flops.flash_step_bytes(conf, 32, 512),
                                   peak)
    assert short["bound"] == "memory"


def test_published_widths_and_reduced_keys():
    """Every published width equals its source (as written in ISSUE 22
    from the public config.json files); only depth is reduced."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {
        "mistral-7b-v0.1-d4": dict(
            hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
            intermediate_size=14336, vocab_size=32000, rope_theta=10000.0,
            rms_norm_eps=1e-5, sliding_window=4096, num_hidden_layers=4,
            tie_word_embeddings=False),
        "deepseek-llm-7b-d20-x4": dict(
            hidden_size=4096, num_attention_heads=32, num_key_value_heads=32,
            intermediate_size=11008, vocab_size=102400, rope_theta=10000.0,
            rms_norm_eps=1e-6, num_hidden_layers=20,
            tie_word_embeddings=False),
    }
    for entry in bench["configs"]:
        conf = _conf(entry["name"])
        for key, value in want[entry["name"]].items():
            assert conf[key] == value, (entry["name"], key)
        assert entry["reduced"] == list(conf["reduced"]) == [
            "num_hidden_layers"]
        assert conf["source"] == entry["source"]


# --------------------------------------------------- reference/decoder.py --

@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_reference_decoder_equals_program_in_float32(kv_heads):
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params, loss_fn

    conf = dict(TINY, num_key_value_heads=kv_heads)
    # the program's flash path (interpreted on the CPU): its
    # attn_impl="reference" does not repeat KV heads without a mesh
    cfg = train.program_config(conf)
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (3, 33), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        program = float(loss_fn(params, {"tokens": tokens}, cfg)[0])
    reference = float(decoder.loss(params, tokens, conf))
    assert abs(program - reference) <= 2e-6 * abs(reference)
    # the tolerance would catch another function of the same weights
    no_rope = float(decoder.loss(params, tokens, dict(conf, rope_theta=1e30)))
    assert abs(no_rope - reference) > 2e-6 * abs(reference)


def test_reference_decoder_blocks_long_queries(monkeypatch):
    """Query blocks change memory only, never the result."""
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params

    cfg = train.program_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65), dtype=np.int32))
    whole = float(decoder.loss(params, tokens, TINY))
    monkeypatch.setattr(decoder, "Q_BLOCK", 16)
    decoder.layer.clear_cache()
    blocked = float(decoder.loss(params, tokens, TINY))
    decoder.layer.clear_cache()
    assert abs(whole - blocked) <= 1e-6 * abs(whole)


# -------------------------------------------------------- trace_reduce.py --

def test_self_times_and_union():
    ev = [("while", 0, 100), ("fusion", 10, 30), ("custom-call.1", 30, 60),
          ("all-reduce.2", 120, 150)]
    assert dict(trace_reduce.self_times(ev)) == {
        "while": 50, "fusion": 20, "custom-call.1": 30, "all-reduce.2": 30}
    assert trace_reduce.union([(0, 100), (10, 30), (120, 150)]) == [
        (0, 100), (120, 150)]


# Event texts as the v5e trace of PR 22 has them (shortened operands).
FLASH = ('%closed_call.11 = (bf16[32,32,512,128]{3,2,1,0:T(8,128)(2,1)}, '
         'f32[32,32,512,128]{3,2,1,0:T(8,128)}) custom-call(bf16[32,32,512,'
         '128]{3,2,1,0} %fusion.406), custom_call_target="tpu_custom_call"')
USES_ONE = ('%fusion.364 = bf16[32,512,4096]{2,1,0:T(8,128)(2,1)} fusion('
            'bf16[4096,4096]{1,0} %custom-call.11), kind=kOutput')
WHILE = ('%while.9 = (s32[]{:T(128)}, bf16[32,512,4096]{2,1,0}) '
         'while((s32[]{:T(128)}) %tuple.1), body=%region_1')
GATHER_START = ('%all-gather-start.1 = (bf16[1024]{0}, bf16[4096]{0}) '
                'all-gather-start(bf16[1024]{0} %p), dimensions={0}')
ALL_REDUCE = ('%all-reduce.26 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} '
              'all-reduce(bf16[2,4096,4096]{2,1,0} %fusion.168), channel_id=57,'
              ' replica_groups=[2,2]<=[4], to_apply=%add.23.clone')
SLICE_START = ('%slice-start.14 = ((bf16[2,4096,16,128]{3,2,1,0}), bf16[1,4096,'
               '16,128]{3,2,1,0}, s32[]{:T(128)}) async-start(bf16[2,4096,16,'
               '128]{3,2,1,0} %gte.1671), calls=%async_computation.14')
GATHER_DONE = ('%all-gather-done.1 = bf16[4096]{0} all-gather-done('
               '(bf16[1024]{0}, bf16[4096]{0}) %all-gather-start.1)')


def test_parse_op():
    assert trace_reduce.parse_op(FLASH) == (
        "closed_call.11", "custom-call", "closed_call.11 custom-call "
        "(bf16[32,32,512,128], f32[32,32,512,128])")
    # an op that READS a custom call's result is no custom call
    assert trace_reduce.parse_op(USES_ONE)[1] == "fusion"
    assert trace_reduce.parse_op(WHILE)[:2] == ("while.9", "while")
    assert trace_reduce.parse_op(GATHER_DONE)[1] == "all-gather-done"
    assert trace_reduce.parse_op(ALL_REDUCE)[1] == "all-reduce"
    # an async slice is data movement on the chip, no collective
    assert not trace_reduce.COLLECTIVE.match(
        trace_reduce.parse_op(SLICE_START)[1])
    assert trace_reduce.parse_op("fusion.3") == ("fusion.3", "fusion",
                                                 "fusion.3")


def test_reduce_synthetic_planes():
    """Two steps after a lead-in: window from the lead-in's end."""
    ops, mods = [], []
    for i, start in enumerate((0, 1000, 2100)):
        mods.append((f"jit_step({i})", start, start + 900))
        ops += [(WHILE, start, start + 600),
                (FLASH, start + 100, start + 300),
                (GATHER_START, start + 600, start + 610),
                (GATHER_DONE, start + 610, start + 700),
                (USES_ONE, start + 700, start + 900)]
    planes = {"/device:TPU:0": {"XLA Ops": sorted(ops, key=lambda e: e[1]),
                                "XLA Modules": mods},
              "/host:CPU": {"python": [("make_batch", 890, 950),
                                       ("report", 1900, 2095)]}}
    out = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                     annotations=("make_batch", "report"))
    d, = out["devices"]
    assert d["steps"] == 2
    assert d["window_s"] == pytest.approx(2100e-9)
    assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"])
    assert d["idle_s"] == pytest.approx(300e-9)
    assert d["gap_s"] == pytest.approx([100e-9, 200e-9])
    assert d["flash_s"] == pytest.approx(400e-9)
    assert d["collective_s"] == pytest.approx(200e-9)
    assert d["collectives_per_step"] == 1
    assert d["idle_gaps"][0] == ["report", pytest.approx(200e-9)]
    assert d["idle_gaps"][1] == ["make_batch", pytest.approx(100e-9)]
    assert trace_reduce.reduce_planes(
        {"/host:CPU": {}}, step_module="jit_step", annotations=()) is None


RECORDED = os.path.join(BENCH, "testdata", "mistral7b-train-s512.xplane.pb.gz")


def test_reduce_recorded_chip_trace(tmp_path):
    """The trace recorded on the v5e in PR 22 (benchmark/testdata): busy +
    idle = window, the step found, the custom calls found."""
    import gzip
    import shutil

    with open(os.path.join(BENCH, "testdata", "expected.json")) as f:
        expected = json.load(f)
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = trace_reduce.reduce_file(
        path, step_module=expected["step_module"],
        annotations=expected["annotations"])
    d, = out["devices"]
    assert d["steps"] == expected["steps"] == len(d["step_s"])
    assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"],
                                                      rel=1e-12)
    assert d["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    idle_pct = 100.0 * d["idle_s"] / d["window_s"]
    assert idle_pct == pytest.approx(expected["idle_pct"], rel=1e-6)
    assert idle_pct + 100.0 * d["busy_s"] / d["window_s"] == \
        pytest.approx(100.0, rel=1e-12)
    # the Mosaic kernels: forward, rematerialised forward, dKV, dQ per
    # layer and step, and nothing that merely reads a custom call's result
    assert d["flash_s"] == pytest.approx(expected["flash_s"], rel=1e-9)
    assert 0.03 < d["flash_s"] / sum(d["step_s"]) < 0.06
    assert statistics.median(d["step_s"]) * 1e3 == pytest.approx(
        expected["step_ms_median"], rel=1e-9)
    assert d["collective_s"] == 0 and d["collectives_per_step"] == 0
    assert len(d["device_ops"]) == 10 and 0 < len(d["idle_gaps"]) <= 5
    assert d["device_ops"][0][0] == expected["top_op"]
    assert all(len(label) <= 120 for label, _ in d["device_ops"])
    assert {label for label, _ in d["idle_gaps"]} <= set(
        expected["annotations"]) | {"unannotated"}
    assert all(out["host_spans"][n] == expected["steps"] + 1
               for n in expected["annotations"])


# ------------------------------------------------------------ the loop --

def _rehearsal_loop(config):
    """Test-only entry: the train loop without the chip requirement."""
    import time

    import jax

    from benchmark.loops import train
    from ray_tpu.air import session

    session.report(train.measure(config, jax.devices(),
                                 {"loop_start": time.time()}))


@pytest.mark.parametrize("mesh", [None, {"fsdp": 2, "tp": 2}],
                         ids=["one-device", "fsdp2-tp2"])
def test_train_loop_rehearsal_on_cpu_worker(mesh):
    """The whole loop at a tiny config through JaxTrainer.fit() with a
    CPU worker.  Asserts the shape of what comes back, no speed."""
    import ray_tpu as ray
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    from benchmark.loops import train

    job = {"loop": "train", "rows": 4, "seq": 64, "mesh": mesh,
           # 8 virtual devices: dp=2 x fsdp=2 split the rows four ways
           "check_rows": 4, "warmup_steps": 2, "traced_steps": 2}
    conf = dict(TINY, assumed={"param_dtype": {"value": "bfloat16"},
                               "dtype": {"value": "bfloat16"}})
    ray.init(num_cpus=4, num_tpus=0)
    try:
        result = JaxTrainer(
            _rehearsal_loop,
            train_loop_config={"conf": conf, "job": job, "chips": 0,
                               "peaks": {}, "seed": 5, "seconds": 1.0,
                               "trace": True, "trace_dir": None},
            scaling_config=ScalingConfig(num_workers=1,
                                         tpu_chips_per_worker=0)).fit()
    finally:
        ray.shutdown()
    assert result.error is None, result.error
    w = result.metrics
    assert w["device"]["platform"] == "cpu"
    win = w["window"]
    assert win["attempted"] == win["steps"] >= 1 and win["failed"] == 0
    assert win["tokens"] == win["steps"] * 4 * 64
    assert win["compiles"] == 0 and win["error"] is None
    assert w["trace"] is None  # a CPU trace has no device plane to read
    # every step reported, as a user's loop does: warm-up, window, traced
    assert len(result.metrics_history) == 2 + win["steps"] + 3 + 1
    # bfloat16 against the float32 reference at a tiny size
    check = w["check"]
    assert abs(check["program_loss"] - check["reference_loss"]) \
        < 2e-2 * check["reference_loss"]
    run = {"worker": w, "process_start": w["loop_start"] - 1.0}
    assert train.end_to_end(run)["train_tokens_per_s"] > 0
    assert train.end_to_end(run)["setup_s"] > 1.0


# -------------------------------------------------------------- run.py --

def test_run_exits_non_zero_without_a_chip():
    """This machine has no chip: no result line, a reason on stderr."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip().startswith("{")


def test_run_holds_no_cell_configuration_or_metric_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "run.py")) as f:
        source = f.read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w["traffic"] for w in bench["workloads"]]
    assert [n for n in names if n in source] == []
    # every name in BENCHMARK.json has its file
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "jobs",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
