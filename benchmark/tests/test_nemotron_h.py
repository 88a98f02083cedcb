"""The yardstick of the Nemotron-H cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_nemotron_h.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_nemotron_h, trace_reduce
from benchmark.loops import train
from benchmark.reference import nemotron_h

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron-twotower-30b-a3b-1of8"
CELL = "nemotronh-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SSM_SCOPES = ["ssm_in", "ssm_conv", "ssm_scan", "ssm_out"]
METRICS = ["ssm.groups_scan_roofline", "ssm.kernel_ms"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share",
               "ssm.time_share_pct", "ssm.scan_ms", "ssm.conv_ms",
               "ssm.out_ms"]    # the last by PR 51, which named both cells
CUT = {"num_hidden_layers": (52, 9), "n_routed_experts": (128, 16),
       "vocab_size": (131072, 16384)}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def _conf():
    return _load("configs", NAME + ".json")


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "experts_held", "vocabulary"]
    # the published string whole; the model is its first 9 characters:
    # 4 : 4 : 1, over the leading period of 7
    assert conf["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6)
    assert nemotron_h.kinds(conf) == tuple("MEMEM*EME")
    assert cuts.period(list(PATTERN)) == 7
    assert conf["share"] == {
        "chips_per_layer": 8, "how": conf["share"]["how"],
        "leading_dense": None}
    assert "WITHOUT the exchange" in conf["deployment"]
    # the file is the stack config.json describes: the second tower absent
    assert "ABSENT" in conf["assumed"]["the_stack"]["value"]
    assert "second, denoising tower" in conf["deployment"]
    assert {"the_stack", "position_embedding_type", "in_proj_order",
            "gated_norm", "scoring_func", "topk_method", "topk_norm_eps",
            "first_expert", "bias_update_speed", "router_aux_loss_coef",
            "initializer", "param_dtype", "dtype", "optimizer", "data"
            } <= set(conf["assumed"])
    assert conf["scopes"] == SSM_SCOPES and conf["kernels"] == ["ssd_"]
    cfg = train.program_config(conf)
    assert [kind for kind, n in cfg.kind_runs if n == 1] == [
        {"M": ("mamba", "none"), "E": ("none", "moe"),
         "*": ("attention", "none")}[c] for c in "MEMEM*EME"]
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.position_embedding, cfg.vocab_size, cfg.norm_eps,
            cfg.tie_embeddings) == (2688, 32, 2, 128, "nope", 16384, 1e-5,
                                    False)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_inner, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk,
            cfg.ssm_conv_dim) == (64, 64, 4096, 128, 8, 4, 128, 6144)
    assert (cfg.ffn_act, cfg.mlp_dim, cfg.shared_experts, cfg.shared_width,
            cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.topk_norm_eps,
            cfg.router_scoring, cfg.select_bias, cfg.routed_scaling_factor,
            cfg.aux_loss_coef) == (
                "relu2", 1856, 1, 3712, 128, 16, 0, 6, True, 1e-20,
                "sigmoid", True, 2.5, 0.0)
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert len(bench["workloads"]) >= 10   # found by name: later cells pass
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-2x8192", 1)
    assert len(cell["why"]) <= 200
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 2, 8192, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == METRICS
    for name in METRICS:
        assert per_layer[name] == {
            "name": name, "unit": per_layer[name]["unit"],
            "better": "higher" if "roofline" in name else "lower",
            "source": "device_trace",
            "layer": per_layer["ssm.scan_roofline"]["layer"],
            "moves": "train_tokens_per_s", "workloads": [CELL]}
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(
                      METRICS + APPENDED_TO)
    # ssm.scan_roofline imports granite's FLOP module and key names
    assert CELL not in per_layer["ssm.scan_roofline"]["workloads"]
    # both four-chip places were taken: this one is a one-chip cell
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(CELL) + 1]
    assert sum(c["chips"] == 4 for c in upto) == 2 == len(upto) // 4
    assert nemotron_h.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "moe_rows_visited_share"} <= set(nemotron_h.STEP_METRICS)


def test_the_parameter_count_is_init_params():
    """The FLOP module's count against the shapes ``init_params`` would make
    (``eval_shape``: nothing is allocated): 986 M for the share."""
    from ray_tpu.models.llama import init_params

    conf = _conf()
    assert flops_nemotron_h.total_params(conf) == 986254848
    shapes = jax.eval_shape(
        lambda k: init_params(k, train.program_config(conf)),
        jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 986254848
    whole = dict(conf, **{k: pub for k, (pub, _) in CUT.items()})
    whole.pop("reduced")
    # the published stack: 31.6 B with both tables
    assert flops_nemotron_h.total_params(whole) == pytest.approx(31.6e9,
                                                                 rel=5e-3)


def test_flops_against_hand_counts():
    """ISSUE 48's arithmetic, counted: 0.745 GFLOP a token forward needed
    here (2.23 forward and backward); the Mamba layers 43 %, the expert
    layers 30 (shared 21, held 8), attention 15, the head over the slice
    12."""
    conf = _conf()
    assert flops.of(conf) is flops_nemotron_h and flops.counts_experts(conf)
    assert [flops_nemotron_h.layers(conf, c) for c in "ME*-"] == [4, 4, 1, 0]
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 4096 * d
    attention = 2 * d * 4096 + 2 * d * 256
    expert, shared = 2 * d * 1856, 2 * d * 3712
    assert (mamba, attention, expert, shared) == (
        38707200, 23396352, 9977856, 19955712)
    assert flops_nemotron_h.held_per_token(conf) == 0.75      # 6 x 16 / 128
    matmul = (4 * mamba + attention
              + 4 * (d * 128 + shared + 0.75 * expert) + d * 16384)
    assert flops_nemotron_h.active_matmul_params(conf) == matmul
    causal = 6 * 8192 * 32 * 128
    pairs = 128 * 129 // 2
    scan = 3 * 4 * (2 * pairs * (8 * 128 + 4096) + 4 * 128 * 128 * 4096) / 128
    assert flops_nemotron_h.ssd_flops_per_token(conf) == scan
    per_token = flops_nemotron_h.train_flops_per_token(conf, 8192)
    assert per_token == 6 * matmul + causal + scan
    assert per_token / 3 == pytest.approx(0.745e9, rel=2e-3)
    for part, share in ((6 * 4 * mamba + scan, 0.430),
                        (6 * 4 * (d * 128 + shared + 0.75 * expert), 0.298),
                        (6 * 4 * shared, 0.214),
                        (6 * 4 * 0.75 * expert, 0.080),
                        (6 * attention + causal, 0.153),
                        (6 * d * 16384, 0.118)):
        assert part / per_token == pytest.approx(share, abs=2e-3)
    # the grouped products: TWO matrices an expert, the rows HELD
    # (16384 x 6 x 16 / 128 = 12288 of 98304 a layer)
    assert flops_nemotron_h.experts_step_flops(conf, 2, 8192) == \
        6 * 16384 * 4 * 0.75 * expert
    rows, weights = 6 * 12288 * (d + 1856) * 2, 3 * 16 * expert * 2
    assert flops_nemotron_h.experts_step_bytes(conf, 2, 8192) == 4 * (
        rows + weights)
    # flash: one layer, 32 query heads over 2 KV heads of 128
    assert flops_nemotron_h.flash_step_flops(conf, 2, 8192) == causal * 16384
    assert flops_nemotron_h.flash_step_bytes(conf, 2, 8192) == \
        6 * 16384 * (32 + 2) * 128 * 2
    # the scan: B and C of 8 groups, by memory
    x, bc, dt = 16384 * 4096 * 2, 2 * 16384 * 8 * 128 * 2, 16384 * 64 * 4
    assert flops_nemotron_h.ssd_step_bytes(conf, 2, 8192) == 4 * (
        5 * x + 3 * (bc + dt))
    assert flops_nemotron_h.ssd_step_flops(conf, 2, 8192) == scan * 16384
    assert flops.roofline_seconds(
        flops_nemotron_h.ssd_step_flops(conf, 2, 8192),
        flops_nemotron_h.ssd_step_bytes(conf, 2, 8192), PEAK)["bound"] == \
        "memory"
    # the structure's ceiling the reader's docstring states
    assert (5 * x + 3 * (bc + dt)) / (7 * x + 4 * (bc + dt)) == \
        pytest.approx(0.723, abs=1e-3)


def _planes(kernel_names=("ssd_fwd", "ssd_bwd")):
    """Three executions of the step (the first a lead-in), each 1000 ns with
    900 ns of ops: a Mamba layer's four scopes (the scan in all three
    phases, as XLA ops, and — where ``kernel_names`` — two Mosaic calls
    under it), attention's three, the four expert scopes with a grouped
    kernel, the shared expert under ``ffn``, the layer scan, the head, the
    optimizer, one bare op."""
    fusion = ('%fusion.{i} = bf16[16384,2688]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[16384,2688]{{1,0}} %p.{i}), kind=kLoop')
    keys = ("in", "conv", "scan_f", "scan_r", "scan_b", "out", "qkv",
            "attn_out", "ffn", "route", "dispatch", "combine", "while",
            "head", "opt", "bare")
    texts = {k: fusion.format(i=i) for i, k in enumerate(keys)}
    call = ('%closed_call.{i} = bf16[16384,2688]{{1,0:T(8,128)(2,1)}} '
            'custom-call(bf16[16384,2688]{{1,0}} %fusion.9{i}), '
            'custom_call_target="tpu_custom_call"')
    for i, key in enumerate(("flash", "gmm", "ssd_f", "ssd_b")):
        texts[key] = call.format(i=i + 1)
    layer = "jit(step)/jvp(while)/body/checkpoint/"
    back = "jit(step)/transpose(jvp(while))/body/"
    stacks = {
        "in": layer + "ssm_in/dot_general",
        "conv": layer + "ssm_conv/mul",
        "scan_f": layer + "ssm_scan/dot_general",
        "scan_r": back + "checkpoint/rematted_computation/ssm_scan/exp",
        "scan_b": back + "transpose(jvp(ssm_scan))/dot_general",
        "out": layer + "ssm_out/dot_general",
        "qkv": layer + "attn_qkv/dot_general",
        "flash": layer + "attention/flash_fwd",
        "attn_out": layer + "attn_out/dot_general",
        "ffn": layer + "ffn/dot_general",
        "route": layer + "moe_route/dot_general",
        "dispatch": layer + "moe_dispatch/gather",
        "gmm": layer + "moe_experts/moe_gmm_relu2",
        "combine": layer + "moe_combine/gather",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
    }
    spans = [("in", 90), ("conv", 30), ("scan_f", 40), ("scan_r", 40),
             ("scan_b", 100), ("out", 50), ("qkv", 30), ("flash", 40),
             ("attn_out", 20), ("ffn", 80), ("route", 20), ("dispatch", 40),
             ("gmm", 90), ("combine", 30), ("while", 20), ("head", 90),
             ("opt", 70), ("bare", 20)]
    if kernel_names:
        stacks["ssd_f"] = layer + f"ssm_scan/{kernel_names[0]}"
        stacks["ssd_b"] = back + (f"transpose(jvp(ssm_scan))/"
                                  f"{kernel_names[1]}")
        # of the scan's 180 ns, 30 + 70 in the kernels
        spans[2:5] = [("scan_f", 10), ("ssd_f", 30), ("scan_r", 40),
                      ("scan_b", 30), ("ssd_b", 70)]
    ops, mods = [], []
    for i in range(3):
        start = 1000 * i
        mods.append((f"jit_step({i})", start, start + 1000))
        for key, ns in spans:
            ops.append((texts[key], start, start + ns))
            start += ns
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
              "/host:CPU": {"python": []}}
    return planes, {"/device:TPU:0": {texts[k]: stacks[k] for k in stacks}}


def _run(trace, conf):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}}},
            "conf": conf, "job": {"rows": 2, "seq": 8192}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 27000.0}}


def _reduced(conf, **kw):
    planes, names = _planes(**kw)
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))


def test_the_two_readers_and_the_sum_to_a_hundred_on_synthetic_planes():
    conf = _conf()
    ns = 1e-9
    # (a) the XLA form, as a model of several groups runs today
    trace = _reduced(conf, kernel_names=())
    d, = trace["devices"]
    assert d["scopes"]["ssm_scan"] == {
        "forward": pytest.approx(40 * ns), "remat": pytest.approx(40 * ns),
        "backward": pytest.approx(100 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    run = _run(trace, conf)
    kernel_ms = _reader("ssm.kernel_ms").read(run)
    assert kernel_ms == 0.0 and isinstance(kernel_ms, float)
    roofline = _reader("ssm.groups_scan_roofline")
    assert roofline.bound(run) == "memory"
    least = flops_nemotron_h.ssd_step_bytes(conf, 2, 8192) / 819e9
    assert least == pytest.approx(4.32e-3, rel=1e-2)
    assert roofline.read(run) == pytest.approx(100 * least / (180 * ns))
    assert _reader("ssm.scan_ms").read(run) == pytest.approx(180e-6)
    assert _reader("ssm.conv_ms").read(run) == pytest.approx(30e-6)
    assert _reader("ssm.time_share_pct").read(run) == pytest.approx(35.0)
    # a step whose scans took 43 ms reads a tenth of the roofline
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["scopes"]["ssm_scan"] = {"forward": 10 * least}
    assert roofline.read(_run(slow, conf)) == pytest.approx(10.0)
    shares = [_reader(m).read(run) or 0.0 for m in (
        "ssm.time_share_pct", "moe.time_share_pct", "step.ffn_pct",
        "step.attn_proj_pct", "step.attention_pct", "step.head_loss_pct",
        "step.optimizer_pct", "step.scan_pct", "step.unscoped_pct")]
    # with the step's idle tenth (900 ns of ops in 1000) they make 100
    assert sum(shares) == pytest.approx(90.0)
    assert _reader("moe.time_share_pct").read(run) == pytest.approx(18.0)
    assert _reader("moe.experts_xla_ms").read(run) == 0.0
    # the ungated experts, through this file's counts: by operations
    experts = _reader("moe.experts_roofline")
    assert experts.bound(run) == "compute"
    assert experts.read(run) == pytest.approx(
        100 * (flops_nemotron_h.experts_step_flops(conf, 2, 8192) / 197e12)
        / (90 * ns))
    # (b) kernels, once a PR brings them for several groups: named through
    # the configuration's "kernels", read on the same scale
    with_kernels = _run(_reduced(conf), conf)
    assert _reader("ssm.kernel_ms").read(with_kernels) == pytest.approx(
        100e-6)
    assert roofline.read(with_kernels) == pytest.approx(roofline.read(run))
    assert _reader("ssm.scan_ms").read(with_kernels) == pytest.approx(180e-6)


def test_on_a_program_without_the_scopes_the_readers_return_nothing():
    """A program that opens no ``ssm_*`` scope, another configuration (whose
    FLOP module counts no scan) and an untraced run: both readers return
    None and neither raises.  granite's cell, whose kernels its file does
    not list, would read ``ssm.kernel_ms`` 0.0: the entry lists this cell
    alone."""
    conf = _conf()
    planes, names = _planes()
    names = {plane: {text: stack.replace("ssm_", "xyz_")
                     for text, stack in stacks.items()}
             for plane, stacks in names.items()}
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=())
    mistral = _load("configs", "mistral-7b-v0.1-d4.json")
    for run in (_run(trace, conf), _run(None, conf), _run(trace, mistral)):
        assert [_reader(m).read(run) for m in METRICS] == [None, None]
    assert _reader("ssm.groups_scan_roofline").read(
        _run(_reduced(conf), mistral)) is None


# The configuration file's own keys at CPU widths: M, E, M, *, E.
TINY = dict(
    _conf(), hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, vocab_size=256, num_hidden_layers=5,
    hybrid_override_pattern="MEM*EMEME", mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=8, n_groups=2, chunk_size=16,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    intermediate_size=32, n_routed_experts=8, num_experts_per_tok=3,
    reduced={"n_routed_experts": {"published": 16, "run": 8}})


def _rehearsal_loop(config):
    """Test-only entry: the train loop without the chip requirement."""
    import time

    from ray_tpu.air import session

    session.report(train.measure(config, jax.devices(),
                                 {"loop_start": time.time()}))


def test_the_one_train_loop_runs_a_tiny_stack_on_a_cpu_worker():
    """The loop every cell runs, on a tiny M E M * E stack through
    ``JaxTrainer.fit()``: the check against ``reference/nemotron_h.py``
    (bfloat16 against float32), the window with its step metrics, the
    traced steps."""
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
    assert check["step_metrics"] == {"moe_dropped": 0.0}
    assert win["step_metrics"]["moe_dropped"] == 0.0
    assert 0.2 < win["step_metrics"]["moe_held_share"] < 0.8
    assert {"loss", "total", "moe_held_share"} <= set(
        check["reference_parts"])
    assert abs(check["program_loss"] - check["reference_loss"]) \
        < 1e-2 * check["reference_loss"]
    assert 0 < check["token_nll_rms"] < 0.2
    assert check["token_nll_limit"] == _conf()["check"]["token_nll_rms"]
    good = dict(w, peak_bytes_in_use=[1], check=dict(
        check, program_loss=check["reference_loss"], token_nll_rms=0.0))
    assert train.correct({"worker": good}) is True
