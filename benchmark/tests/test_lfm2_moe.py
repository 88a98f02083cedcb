"""The yardstick of the LFM2-8B-A1B cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_lfm2_moe.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_lfm2, trace_reduce
from benchmark.loops import train
from benchmark.reference import lfm2_moe

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-8b-a1b-1of2"
CELL = "lfm2moe-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SCONV_SCOPES = ["sconv_in", "sconv_gate", "sconv_out"]
METRICS = ["sconv.time_share_pct", "sconv.gate_ms", "sconv.gate_roofline"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.held_rows_share", "moe.rows_visited_share"]
# the readers later PRs brought and listed this cell under (PRs 41, 47)
LATER = ["moe.token_rows_read_share", "moe.experts_xla_ms"]
CUT = {"num_hidden_layers": (24, 8), "num_experts": (32, 16),
       "vocab_size": (65536, 32768)}


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


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_two():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "experts_held", "vocabulary"]
    # the published list whole; the model is its first 8 entries: 6 : 2
    assert len(conf["layer_types"]) == 24
    ops = conf["layer_types"][:conf["num_hidden_layers"]]
    assert (ops.count("conv"), ops.count("full_attention")) == (6, 2)
    assert cuts.period(conf["layer_types"][conf["num_dense_layers"]:]) == 4
    assert conf["share"] == {
        "chips_per_layer": 2, "how": conf["share"]["how"],
        "leading_dense": "num_dense_layers"}
    assert "WITHOUT the exchange" in conf["deployment"]
    # what the public file does not state is explained, a key each
    assert {"scoring_func", "topk_method", "topk_norm_eps", "qk_head_norm",
            "tie_word_embeddings", "bias_update_speed", "first_expert",
            "router_aux_loss_coef", "conv_layout", "block", "rope_pairing",
            "initializer", "param_dtype", "dtype", "optimizer", "data"
            } <= set(conf["assumed"])
    assert conf["scopes"] == SCONV_SCOPES and "kernels" not in conf
    cfg = train.program_config(conf)
    assert [(kind, n) for kind, n in cfg.kind_runs] == [
        (("conv", "dense"), 2), (("full_attention", "moe"), 1),
        (("conv", "moe"), 3), (("full_attention", "moe"), 1),
        (("conv", "moe"), 1)]
    assert tuple(lfm2_moe.kinds(conf)) == cfg.layer_kinds
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.dense_width, cfg.vocab_size, cfg.norm_eps,
            cfg.rope_theta, cfg.tie_embeddings) == (
                2048, 32, 8, 64, 1792, 7168, 32768, 1e-5, 1e6, True)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.topk_norm_eps,
            cfg.router_scoring, cfg.select_bias, cfg.routed_scaling_factor,
            cfg.shared_experts, cfg.aux_loss_coef) == (
                32, 16, 0, 4, True, 1e-6, "sigmoid", True, 1, 0, 0.0)
    assert (cfg.sconv_width, cfg.qk_head_norm, cfg.qk_norm) == (
        3, True, False)
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-1x8192", 1)
    assert len(cell["why"]) <= 200
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"]) == ("train", 1, 8192, None, 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == METRICS
    # appended side by side and in this order, wherever the list ends now
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(
                      METRICS + APPENDED_TO + LATER)
    # a one-chip cell (above); how many cells may take four is ONE rule:
    # test_benchmark.py::test_at_most_a_quarter_of_the_cells_take_four_chips
    assert lfm2_moe.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "moe_rows_visited_share"} <= set(lfm2_moe.STEP_METRICS)


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1334254016),
                                         (True, 8339930560)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share and of the
    published model, 8.34 B with the head tied."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_lfm2.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_against_hand_counts():
    """ISSUE 40's arithmetic: 2.66 GFLOP a token here; the held experts
    29.8 %, the conv mixers' projections 22.7, the dense FFNs 19.9, the
    head over the slice 15.2, attention 7.6, its projections 4.7."""
    conf = _conf()
    assert flops.of(conf) is flops_lfm2 and flops.counts_experts(conf)
    assert flops.attention_layers(conf) == 6      # the trap: the whole list
    assert (flops_lfm2.attention_layers(conf), flops_lfm2.conv_layers(conf),
            flops_lfm2.expert_layers(conf)) == (2, 6, 6)
    conv, attention = 4 * 2048 ** 2, 2 * 2048 ** 2 + 2 * 2048 * 512
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    assert (conv, attention, dense, expert) == (
        16777216, 10485760, 44040192, 11010048)
    assert flops_lfm2.held_per_token(conf) == 2.0       # 4 x 16 / 32
    matmul = (6 * conv + 2 * attention + 2 * dense
              + 6 * (2048 * 32 + 2 * expert) + 2048 * 32768)
    assert flops_lfm2.active_matmul_params(conf) == matmul == 409337856
    causal = 6 * 2 * 8192 * 32 * 64
    per_token = flops_lfm2.train_flops_per_token(conf, 8192)
    assert per_token == 6 * matmul + causal == 2657353728
    for part, share in ((6 * 6 * 2 * expert, 0.298), (6 * 6 * conv, 0.227),
                        (6 * 2 * dense, 0.199), (6 * 2048 * 32768, 0.152),
                        (causal, 0.076), (6 * 2 * attention, 0.047)):
        assert part / per_token == pytest.approx(share, abs=1e-3)
    # at the published depth, experts and vocabulary the head is 8 %
    whole = _whole(conf)
    assert flops_lfm2.held_per_token(whole) == 4.0
    assert 6 * 2048 * 65536 / flops_lfm2.train_flops_per_token(
        whole, 8192) == pytest.approx(0.081, abs=1e-3)
    # the grouped products over the rows HELD: 16384 of 32768 a layer
    assert flops_lfm2.experts_step_flops(conf, 1, 8192) == \
        6 * 8192 * 6 * 2 * expert
    rows, weights = 9 * 16384 * (2048 + 1792) * 2, 3 * 16 * expert * 2
    assert flops_lfm2.experts_step_bytes(conf, 1, 8192) == 6 * (
        rows + weights)
    # flash: two GQA layers at head size 64
    assert flops_lfm2.flash_step_flops(conf, 1, 8192) == causal * 8192
    assert flops_lfm2.flash_step_bytes(conf, 1, 8192) == \
        2 * 6 * 8192 * (32 + 8) * 64 * 2
    # the gated short convolution: 369 MB and 369 MFLOP a layer, by memory
    layer = 11 * 8192 * 2048 * 2
    assert layer == 369098752
    assert flops_lfm2.sconv_step_bytes(conf, 1, 8192) == 6 * layer
    assert flops_lfm2.sconv_step_flops(conf, 1, 8192) == \
        6 * 8192 * 2048 * (7 + 15)
    assert flops.roofline_seconds(
        flops_lfm2.sconv_step_flops(conf, 1, 8192),
        flops_lfm2.sconv_step_bytes(conf, 1, 8192), PEAK) == {
            "seconds": 6 * layer / 819e9, "bound": "memory"}
    assert 6 * layer / 819e9 == pytest.approx(2.70e-3, rel=1e-2)


def _planes():
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops: the three short-convolution scopes (the gate in
    all three phases), attention's three, the four expert scopes, the
    dense FFN, the layer scan, the head, the optimizer, one bare op."""
    fusion = ('%fusion.{i} = bf16[8192,2048]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[8192,2048]{{1,0}} %p.{i}), kind=kLoop')
    keys = ("in", "gate_f", "gate_r", "gate_b", "out", "qkv", "attn_out",
            "ffn", "route", "dispatch", "combine", "while", "head", "opt",
            "bare")
    texts = {k: fusion.format(i=i) for i, k in enumerate(keys)}
    call = ('%closed_call.{i} = bf16[8192,2048]{{1,0:T(8,128)(2,1)}} '
            'custom-call(bf16[8192,2048]{{1,0}} %fusion.9{i}), '
            'custom_call_target="tpu_custom_call"')
    texts["flash"], texts["gmm"] = call.format(i=1), call.format(i=2)
    layer = "jit(step)/jvp(while)/body/checkpoint/"
    back = "jit(step)/transpose(jvp(while))/body/"
    stacks = {
        "in": layer + "sconv_in/dot_general",
        "gate_f": layer + "sconv_gate/mul",
        "gate_r": back + "checkpoint/rematted_computation/sconv_gate/mul",
        "gate_b": back + "transpose(jvp(sconv_gate))/mul",
        "out": layer + "sconv_out/dot_general",
        "qkv": layer + "attn_qkv/dot_general",
        "flash": layer + "attention/flash_fwd",
        "attn_out": layer + "attn_out/dot_general",
        "ffn": layer + "ffn/dot_general",
        "route": layer + "moe_route/dot_general",
        "dispatch": layer + "moe_dispatch/gather",
        "gmm": layer + "moe_experts/moe_gmm",
        "combine": layer + "moe_combine/gather",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
    }
    spans = [("in", 90), ("gate_f", 20), ("gate_r", 20), ("gate_b", 40),
             ("out", 40), ("qkv", 30), ("flash", 40), ("attn_out", 20),
             ("ffn", 120), ("route", 20), ("dispatch", 50), ("gmm", 150),
             ("combine", 40), ("while", 20), ("head", 110), ("opt", 70),
             ("bare", 20)]
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


def _run(trace, conf):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}}},
            "conf": conf, "job": {"rows": 1, "seq": 8192}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 27000.0}}


def test_sconv_readers_and_the_sum_to_a_hundred_on_synthetic_planes():
    conf = _conf()
    planes, names = _planes()
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))
    d, = trace["devices"]
    ns = 1e-9
    assert d["scopes"]["sconv_gate"] == {
        "forward": pytest.approx(20 * ns), "remat": pytest.approx(20 * ns),
        "backward": pytest.approx(40 * ns)}
    assert d["scopes"]["sconv_in"] == {"forward": pytest.approx(90 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    run = _run(trace, conf)
    assert _reader("sconv.time_share_pct").read(run) == pytest.approx(21.0)
    assert _reader("sconv.gate_ms").read(run) == pytest.approx(80e-6)
    roofline = _reader("sconv.gate_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * (6 * 369098752 / 819e9) / (80 * ns))
    # a step whose gates took 27 ms reads a tenth of the roofline, one
    # that took the ceiling's 3.68 ms (the forward twice) reads 73 %
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["scopes"]["sconv_gate"] = {"forward": 0.027}
    assert roofline.read(_run(slow, conf)) == pytest.approx(10.0, rel=1e-2)
    slow["devices"][0]["scopes"]["sconv_gate"] = {
        "forward": 6 * 503316480 / 819e9}
    assert roofline.read(_run(slow, conf)) == pytest.approx(73.3, abs=0.1)
    shares = [_reader(m).read(run) or 0.0 for m in (
        "sconv.time_share_pct", "moe.time_share_pct", "step.ffn_pct",
        "step.attn_proj_pct", "step.attention_pct", "step.head_loss_pct",
        "step.optimizer_pct", "step.scan_pct", "step.unscoped_pct")]
    # with the step's idle tenth (900 ns of ops in 1000) they make 100
    assert sum(shares) == pytest.approx(90.0)
    assert _reader("moe.time_share_pct").read(run) == pytest.approx(26.0)
    assert _reader("moe.dispatch_ms").read(run) is not None


def test_on_a_program_without_the_scopes_the_readers_return_nothing():
    """The parent's program opens no ``sconv_*`` scope, and another
    configuration's FLOP module counts no short convolution: every reader
    returns None and none raises; an untraced run likewise."""
    conf = _conf()
    planes, names = _planes()
    names = {plane: {text: stack.replace("sconv_", "xyz_")
                     for text, stack in stacks.items()}
             for plane, stacks in names.items()}
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=())
    for run in (_run(trace, conf), _run(None, conf),
                _run(trace, _load("configs", "mistral-7b-v0.1-d4.json"))):
        assert [_reader(m).read(run) for m in METRICS] == [None] * 3
