"""The yardstick of the granite-4.0-h-micro cell: ``JAX_PLATFORMS=cpu python
-m pytest benchmark/tests/test_granite_hybrid.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import pytest

from benchmark import flops, flops_hybrid, trace_reduce
from benchmark.loops import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "granite-4.0-h-micro-d10"
CELL = "granite4h-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SSM_SCOPES = ["ssm_in", "ssm_conv", "ssm_scan", "ssm_out"]


def _conf():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_is_the_catalog_row_and_the_model_its_first_ten_layers():
    conf = _conf()
    assert (conf["num_hidden_layers"], len(conf["layer_types"])) == (10, 40)
    assert [i for i, k in enumerate(conf["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert conf["scopes"] == SSM_SCOPES and "kernels" not in conf
    cfg = train.program_config(conf)
    assert cfg.layer_runs == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps) == (
                2048, 32, 8, 64, 8192, 100352, 1e-5)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.ssm_inner, cfg.ssm_conv_dim
            ) == (64, 64, 128, 1, 4, 256, 4096, 4352)
    assert (cfg.position_embedding, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.tie_embeddings) == (
                "nope", 1 / 64, 12, 0.22, 8, True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-1x8192", 1)
    # the four entries this cell brought: its name leads their lists (a
    # later state-space cell is appended to three; the roofline imports
    # this model's FLOP module and stays this cell's alone)
    ours = [m for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == CELL]
    assert [m["name"] for m in ours][:4] == [
        "ssm.time_share_pct", "ssm.scan_ms", "ssm.conv_ms",
        "ssm.scan_roofline"]
    assert ours[3]["workloads"] == [CELL]
    assert {m["layer"] for m in ours} == {"state-space mixer"}
    # a one-chip cell (above); how many cells may take four is ONE rule:
    # test_benchmark.py::test_at_most_a_quarter_of_the_cells_take_four_chips


def test_flops_hybrid_against_hand_counts():
    """ISSUE 30's arithmetic: ONE attention layer although the file lists
    four, 952 M matmul parameters, the tied table once."""
    conf = _conf()
    assert flops.of(conf) is flops_hybrid
    assert flops.attention_layers(conf) == 4      # the trap: the whole list
    assert (flops_hybrid.attention_layers(conf),
            flops_hybrid.mamba_layers(conf)) == (1, 9)
    mlp = 3 * 2048 * 8192
    mamba = 2048 * (4096 + 4352 + 64) + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (mamba + mlp, attention + mlp) == (76152832, 60817408)
    matmul = 9 * (mamba + mlp) + attention + mlp + 2048 * 100352
    assert flops_hybrid.matmul_params(conf) == matmul == 951713792
    causal = 6 * 8192 * 32 * 64
    pairs = 256 * 257 // 2
    ssd = 3 * 9 * (2 * pairs * (128 + 4096) + 4 * 256 * 128 * 4096) / 256
    assert flops_hybrid.ssd_flops_per_token(conf) == ssd
    per_token = flops_hybrid.train_flops_per_token(conf, 8192)
    assert per_token == 6 * matmul + causal + ssd
    assert per_token == pytest.approx(5.897e9, rel=1e-3)
    assert 6 * 2048 * 100352 / per_token == pytest.approx(0.209, abs=1e-3)
    whole = dict(conf, num_hidden_layers=40)     # the published depth
    assert (flops_hybrid.attention_layers(whole),
            flops_hybrid.mamba_layers(whole)) == (4, 36)
    assert 6 * 2048 * 100352 / flops_hybrid.train_flops_per_token(
        whole, 8192) == pytest.approx(0.063, abs=1e-3)
    # the scan's own counts: 704 GFLOP and 3.19 GB a step
    assert flops_hybrid.ssd_step_flops(conf, 1, 8192) == ssd * 8192
    assert flops_hybrid.ssd_step_flops(conf, 1, 8192) == pytest.approx(
        703.97e9, rel=1e-4)
    x, bc, dt = 8192 * 4096 * 2, 2 * 8192 * 128 * 2, 8192 * 64 * 4
    assert flops_hybrid.ssd_step_bytes(conf, 1, 8192) == 9 * (
        (2 * x + bc + dt) + (3 * x + 2 * (bc + dt))) == 3189768192
    assert flops.roofline_seconds(
        flops_hybrid.ssd_step_flops(conf, 1, 8192),
        flops_hybrid.ssd_step_bytes(conf, 1, 8192), PEAK) == {
            "seconds": 3189768192 / 819e9, "bound": "memory"}
    # flash: one layer at head size 64
    assert flops_hybrid.flash_step_flops(conf, 1, 8192) == \
        6 * 8192 ** 2 * 32 * 64
    assert flops_hybrid.flash_step_bytes(conf, 1, 8192) == \
        6 * 8192 * (32 + 8) * 64 * 2


def test_flash_roofline_counts_one_layer_of_the_four_listed():
    """On a run whose one flash layer took 10 ms a step, the reader gives
    a quarter of what ``flops.py``'s count of the whole list would."""
    conf = _conf()
    device = {"steps": 2, "flash_s": 0.020, "step_s": [0.5, 0.5]}
    run = {"conf": conf, "job": {"rows": 1, "seq": 8192}, "chips": 1,
           "peak": PEAK, "worker": {"trace": {"devices": [device]}},
           "end_to_end": {"train_tokens_per_s": 17000.0}}
    roofline = _reader("flash_roofline")
    got = roofline.read(run)
    assert got == pytest.approx(
        100 * 6 * 8192 ** 2 * 32 * 64 / 197e12 / 0.010)
    assert 0 < got < 100 and roofline.bound(run) == "compute"
    assert got == pytest.approx(
        roofline.read(dict(run, conf=dict(conf, flops="flops"))) / 4)
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 17000.0 * flops_hybrid.train_flops_per_token(conf, 8192)
        / 197e12)


def _planes():
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops: the four Mamba scopes, the dense FFN, the layer
    scan, the flash kernel, the head, the optimizer and one bare op."""
    fusion = ('%fusion.{i} = bf16[8192,4096]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[8192,4096]{{1,0}} %p.{i}), kind=kLoop')
    texts = {k: fusion.format(i=i) for i, k in enumerate(
        ("in", "conv", "scan_f", "scan_r", "scan_b", "out", "ffn", "while",
         "head", "opt", "bare"))}
    texts["flash"] = (
        '%closed_call.3 = (bf16[1,32,8192,64]{3,2,1,0:T(8,128)(2,1)}, '
        'f32[1,32,8192,128]{3,2,1,0:T(8,128)}) custom-call(bf16[1,32,8192,64]'
        '{3,2,1,0} %fusion.99), custom_call_target="tpu_custom_call"')
    stacks = {
        "in": "jit(step)/jvp(while)/body/checkpoint/ssm_in/dot_general",
        "conv": "jit(step)/jvp(while)/body/checkpoint/ssm_conv/mul",
        "scan_f": "jit(step)/jvp(while)/body/checkpoint/ssm_scan/dot_general",
        "scan_r": "jit(step)/transpose(jvp(while))/body/checkpoint/"
                  "rematted_computation/ssm_scan/exp",
        "scan_b": "jit(step)/transpose(jvp(while))/body/transpose(jvp("
                  "ssm_scan))/dot_general",
        "out": "jit(step)/jvp(while)/body/checkpoint/ssm_out/dot_general",
        "ffn": "jit(step)/jvp(while)/body/checkpoint/ffn/dot_general",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
        "flash": "jit(step)/jvp(while)/body/checkpoint/attention/flash_fwd",
    }
    spans = [("in", 100), ("conv", 30), ("scan_f", 40), ("scan_r", 40),
             ("scan_b", 70), ("out", 60), ("ffn", 250), ("while", 20),
             ("flash", 50), ("head", 150), ("opt", 70), ("bare", 20)]
    ops, mods = [], []
    for i in range(3):
        start = 1000 * i
        mods.append((f"jit_step({i})", start, start + 1000))
        for key, ns in spans:
            ops.append((texts[key], start, start + ns))
            start += ns
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
              "/host:CPU": {"python": []}}
    return planes, {"/device:TPU:0": {texts[k]: stacks[k] for k in texts}}


def test_ssm_readers_and_the_sum_to_a_hundred_on_synthetic_planes():
    conf = _conf()
    planes, names = _planes()
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))
    d, = trace["devices"]
    ns = 1e-9
    assert d["steps"] == 2 and sum(d["step_s"]) / 2 == pytest.approx(1000 * ns)
    assert d["scopes"]["ssm_scan"] == {
        "forward": pytest.approx(40 * ns), "remat": pytest.approx(40 * ns),
        "backward": pytest.approx(70 * ns)}
    assert d["scopes"]["ssm_in"] == {"forward": pytest.approx(100 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    assert d["kernels"] == {"flash_fwd": pytest.approx(50 * ns)}
    run = {"worker": {"trace": trace, "window": {"step_metrics": {}}},
           "conf": conf, "job": {"rows": 1, "seq": 8192}, "chips": 1,
           "peak": PEAK, "end_to_end": {"train_tokens_per_s": 17000.0}}
    assert _reader("ssm.time_share_pct").read(run) == pytest.approx(34.0)
    assert _reader("ssm.scan_ms").read(run) == pytest.approx(150e-6)
    assert _reader("ssm.conv_ms").read(run) == pytest.approx(30e-6)
    roofline = _reader("ssm.scan_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * (3189768192 / 819e9) / (150 * ns))
    # a step whose scans took 40 ms reads under the structure's ceiling
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["scopes"]["ssm_scan"] = {"forward": 0.040}
    assert roofline.read(dict(run, worker={"trace": slow})) == \
        pytest.approx(9.737, abs=1e-3)
    shares = {m: _reader("step." + m + "_pct").read(run) for m in (
        "ffn", "attn_proj", "attention", "head_loss", "optimizer", "scan",
        "unscoped")}
    assert shares == {
        "ffn": pytest.approx(25.0), "attn_proj": None,
        "attention": pytest.approx(5.0), "head_loss": pytest.approx(15.0),
        "optimizer": pytest.approx(7.0), "scan": pytest.approx(2.0),
        "unscoped": pytest.approx(2.0)}
    assert sum(v for v in shares.values() if v) + _reader(
        "ssm.time_share_pct").read(run) == pytest.approx(90.0)  # 10 % idle
    assert _reader("step.remat_pct").read(run) == pytest.approx(4.0)

    # a program without the scopes (the parent), or a run without a
    # trace: nothing to read, nothing raised; without the configuration's
    # names the Mamba ops fall to the scan's row
    bare = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                      annotations=())
    unnamed = trace_reduce.reduce_planes(planes, step_module="jit_step",
                                         annotations=(), names=names)
    assert "ssm_scan" not in unnamed["devices"][0]["scopes"]
    for worker in ({"trace": bare}, {"trace": unnamed}, {"trace": None}):
        for metric in ("ssm.time_share_pct", "ssm.scan_ms", "ssm.conv_ms",
                       "ssm.scan_roofline"):
            assert _reader(metric).read(dict(run, worker=worker)) is None


# The configuration file's own keys at CPU widths: mamba, attention, mamba.
TINY = dict(
    _conf(), hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, intermediate_size=96, vocab_size=256,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba", "mamba"],
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8, mamba_n_groups=1,
    mamba_chunk_size=16, attention_multiplier=0.25)


def _rehearsal_loop(config):
    """Test-only entry: the train loop without the chip requirement."""
    import time

    import jax

    from ray_tpu.air import session

    session.report(train.measure(config, jax.devices(),
                                 {"loop_start": time.time()}))


def test_the_one_train_loop_runs_a_tiny_hybrid_on_a_cpu_worker():
    """The loop every cell runs, on a tiny hybrid through
    ``JaxTrainer.fit()``: the check against ``reference/granite_hybrid.py``
    (bfloat16 against float32), the window, the traced steps."""
    import ray_tpu as ray
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    job = {"loop": "train", "rows": 2, "seq": 64, "mesh": None,
           "check_rows": 2, "warmup_steps": 2, "traced_steps": 2}
    ray.init(num_cpus=4, num_tpus=0)
    try:
        result = JaxTrainer(
            _rehearsal_loop,
            train_loop_config={"conf": TINY, "job": job, "chips": 0,
                               "peaks": {}, "seed": 2147483659,
                               "seconds": 1.0, "trace": True,
                               "trace_dir": None},
            scaling_config=ScalingConfig(num_workers=1,
                                         tpu_chips_per_worker=0)).fit()
    finally:
        ray.shutdown()
    assert result.error is None, result.error
    w = result.metrics
    win, check = w["window"], w["check"]
    assert win["attempted"] == win["steps"] >= 1 and win["failed"] == 0
    assert win["compiles"] == 0 and win["error"] is None
    assert check["step_metrics"] == win["step_metrics"] == {}
    assert set(check["reference_parts"]) == {"loss", "total"}
    assert abs(check["program_loss"] - check["reference_loss"]) \
        < 5e-3 * check["reference_loss"]
    assert 0 < check["token_nll_rms"] < 0.05
    assert check["token_nll_limit"] == _conf()["check"]["token_nll_rms"]
    good = dict(w, peak_bytes_in_use=[1], check=dict(
        check, program_loss=check["reference_loss"], token_nll_rms=0.0))
    assert train.correct({"worker": good}) is True


def test_the_reference_tells_the_program_in_float32_from_another_model():
    """At CPU size in float32 the program and the reference agree to
    1e-5 in every token's loss; the same weights read as a model whose
    attention scale is 1/sqrt(d) do not."""
    import jax
    import numpy as np

    from benchmark.reference import granite_hybrid
    from ray_tpu.models.llama import forward, init_params

    conf = dict(TINY, assumed={"param_dtype": {"value": "float32"},
                               "dtype": {"value": "float32"}})
    cfg = train.program_config(conf)
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 49), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        _, _, nll = jax.jit(train.program_check(cfg, None))(params, tokens)
    want = granite_hybrid.loss_parts(params, tokens, conf)["token_nll"]
    assert float(abs(nll - want).max()) < 1e-5
    other = granite_hybrid.loss_parts(
        params, tokens, dict(conf, attention_multiplier=0.5))["token_nll"]
    assert float(abs(other - want).max()) > 1e-4
    # layer(x, layers, 0, ...): what the compile rehearsal lowers
    x = jax.numpy.ones((2, 48, 64), jax.numpy.float32)
    out = granite_hybrid.layer(x, params["layers"], 0,
                               **granite_hybrid.layer_kwargs(conf))
    assert out.shape == x.shape
