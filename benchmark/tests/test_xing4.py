"""The yardstick of the Xing4.0-29B-A4B cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_xing4.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import pytest

from benchmark import cuts, flops, flops_xing4, trace_reduce
from benchmark.loops import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "xing4.0-29b-a4b-1of8"
CELL = "xing4-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
D, HEADS, TOKENS = 3584, 32, 8192


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


def test_the_file_is_the_catalog_row_cut_to_one_chips_share_of_eight():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (v["published"], v["run"], v["kind"])
            for k, v in conf["reduced"].items()} == {
        "num_hidden_layers": (40, 6, "depth"),
        "n_routed_experts": (64, 8, "experts_held"),
        "vocab_size": (131072, 16384, "vocabulary")}
    assert conf["share"]["chips_per_layer"] == 8
    assert conf["share"]["leading_dense"] == "first_k_dense_replace"
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
                "n_shared_experts", "num_experts_per_tok", "hc_mult",
                "hc_sinkhorn_iters", "num_nextn_predict_layers",
                "rope_scaling", "first_k_dense_replace"):
        assert conf[key] == published[key], key
    assert conf["scopes"] == ["hc_map", "hc_mix", "mtp_in"]
    assert "kernels" not in conf       # flash_ and moe_ are trace_scopes' own
    # a cut that the rule refuses: a width, or fewer experts than the floor
    assert cuts.complaints(dict(conf, kv_lora_rank=256), published)
    few = dict(conf, n_routed_experts=4, reduced=dict(
        conf["reduced"], n_routed_experts=dict(
            conf["reduced"]["n_routed_experts"], run=4)))
    assert any("experts held" in c or "chips_per_layer" in c
               for c in cuts.complaints(few, published))


def test_the_program_is_told_both_counts_and_every_published_number():
    fields = train.program_fields(_conf())
    assert (fields["num_experts"], fields["experts_held"],
            fields["first_expert"], fields["num_selected"]) == (64, 8, 0, 4)
    assert (fields["vocab_size"], fields["num_layers"],
            fields["leading_dense"]) == (16384, 6, 2)
    assert (fields["embed_dim"], fields["mlp_dim"], fields["dense_mlp_dim"],
            fields["num_heads"]) == (3584, 1024, 9216, 32)
    assert (fields["q_lora_rank"], fields["kv_lora_rank"],
            fields["qk_nope_dim"], fields["qk_rope_dim"],
            fields["v_head_dim"]) == (768, 512, 128, 64, 128)
    assert fields["rope_scaling"]["factor"] == 64
    assert (fields["router_scoring"], fields["topk_method"],
            fields["routed_scaling_factor"], fields["norm_topk_prob"],
            fields["shared_experts"]) == ("sigmoid", "noaux_tc", 2, True, 1)
    assert (fields["hc_mult"], fields["hc_sinkhorn_iters"], fields["hc_eps"],
            fields["hc_clamp_min"], fields["hc_clamp_max"]) == (
                4, 20, 1e-6, -30, 30)
    assert (fields["num_nextn"], fields["mtp_loss_coef"],
            fields["bias_update_speed"], fields["aux_loss_coef"]) == (
                1, 0.3, 0.001, 0.0)
    cfg = train.program_config(_conf())
    assert cfg.kind_runs == ((("latent", "dense"), 2), (("latent", "moe"), 4))
    assert cfg.mtp_runs == ((("latent", "moe"), 1),)
    assert cfg.latent_qk_dim == 192 and cfg.local_experts == 8


def test_the_cell_its_job_and_its_metrics():
    bench = _load("..", "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-1x8192", 1)
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["check_rows"],
            job["warmup_steps"], job["traced_steps"], job["mesh"]) == (
                "train", 1, 8192, 1, 2, 4, None)
    # the six entries this cell brought: its name leads their lists, and
    # the cells later PRs appended to two of them come after it
    ours = [m["name"] for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == CELL]
    assert ours == ["hc.time_share_pct", "hc.map_ms", "hc.mix_ms",
                    "hc.mix_roofline", "mtp.in_pct", "moe.held_rows_share"]
    for name in ours[:4]:
        metric, = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL], name
    for name in ("moe.experts_roofline", "moe.load_max_over_mean"):
        metric, = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"][:2] == ["olmoe-train-s4096", CELL]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    # a one-chip cell (above); how many cells may take four is ONE rule:
    # test_benchmark.py::test_at_most_a_quarter_of_the_cells_take_four_chips


def test_flops_xing4_against_hand_counts():
    """ISSUE 34's table: what THIS chip holds and computes."""
    conf = _conf()
    assert flops.of(conf) is flops_xing4 and flops.counts_experts(conf)
    attention = (D * 768 + 768 * HEADS * 192 + D * 576
                 + 512 * HEADS * 256 + HEADS * 128 * D)
    assert flops_xing4.attention_params(conf) == attention == 28409856
    expert, maps = 3 * D * 1024, 4 * D * 24
    assert flops_xing4.expert_params(conf) == expert == 11010048
    assert flops_xing4.map_params(conf) == maps == 344064
    assert (flops_xing4.blocks(conf), flops_xing4.expert_layers(conf),
            flops_xing4.published_experts(conf)) == (7, 5, 64)
    assert flops_xing4.held_per_token(conf) == 0.5
    active = (7 * (attention + 2 * maps) + 2 * 3 * D * 9216
              + 5 * (D * 64 + expert + 0.5 * expert)
              + 2 * D * 16384 + 2 * D * D)
    assert flops_xing4.active_matmul_params(conf) == active
    assert flops_xing4.total_params(conf) == pytest.approx(1041.5e6,
                                                           rel=5e-4)
    causal = 3 * 7 * TOKENS * HEADS * (192 + 128)
    assert flops_xing4.attention_flops_per_token(conf, TOKENS) == causal
    mix = 3 * 2 * 7 * 2 * 4 * D * 6
    assert flops_xing4.mix_flops_per_token(conf) == mix
    per_token = flops_xing4.train_flops_per_token(conf, TOKENS)
    assert per_token == 6 * active + causal + mix
    assert per_token == pytest.approx(5.541e9, rel=1e-3)
    assert causal / per_token == pytest.approx(0.318, abs=2e-3)
    # the kernels' own counts
    assert flops_xing4.flash_step_flops(conf, 1, TOKENS) == causal * TOKENS
    assert flops_xing4.flash_step_bytes(conf, 1, TOKENS) == 7 * 3 * TOKENS * (
        HEADS * 192 + HEADS * 128 + 64 + 2 * HEADS * 128) * 2
    assert flops_xing4.experts_step_flops(conf, 1, TOKENS) == \
        6 * TOKENS * 5 * 0.5 * expert
    assert flops_xing4.experts_step_bytes(conf, 1, TOKENS) == 5 * (
        9 * TOKENS * 0.5 * (D + 1024) * 2 + 3 * 8 * expert * 2)
    assert flops_xing4.hc_step_bytes(conf, 1, TOKENS) == \
        14 * TOKENS * (9 * 4 * D + 5 * D) * 2 == 33705426944
    assert flops.roofline_seconds(
        flops_xing4.hc_step_flops(conf, 1, TOKENS),
        flops_xing4.hc_step_bytes(conf, 1, TOKENS), PEAK) == {
            "seconds": 33705426944 / 819e9, "bound": "memory"}
    # the uncut layer would count four experts a token, not half of one
    whole = dict(conf, n_routed_experts=64, reduced={})
    assert flops_xing4.held_per_token(whole) == 4


def _planes():
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops under the scopes this model opens."""
    fusion = ('%fusion.{i} = bf16[8192,14336]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[8192,14336]{{1,0}} %p.{i}), kind=kLoop')
    keys = ("map_f", "map_b", "mix_f", "mix_r", "mix_b", "qkv", "ffn",
            "experts", "route", "mtp", "head", "while", "opt", "bare")
    texts = {k: fusion.format(i=i) for i, k in enumerate(keys)}
    texts["flash"] = (
        '%closed_call.3 = (bf16[1,32,8192,128]{3,2,1,0:T(8,128)(2,1)}, '
        'f32[1,32,8192,128]{3,2,1,0:T(8,128)}) custom-call(bf16[1,32,8192,'
        '192]{3,2,1,0} %fusion.99), custom_call_target="tpu_custom_call"')
    body = "jit(step)/jvp(while)/body/checkpoint/"
    back = "jit(step)/transpose(jvp(while))/body/"
    stacks = {
        "map_f": body + "hc_map/dot_general",
        "map_b": back + "transpose(jvp(hc_map))/mul",
        "mix_f": body + "hc_mix/mul",
        "mix_r": back + "checkpoint/rematted_computation/hc_mix/mul",
        "mix_b": back + "transpose(jvp(hc_mix))/mul",
        "qkv": body + "attn_qkv/dot_general",
        "ffn": body + "ffn/dot_general",
        "experts": body + "moe_experts/moe_gmm",
        "route": body + "moe_route/dot_general",
        "mtp": "jit(step)/jvp(mtp_in)/dot_general",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
        "flash": body + "attention/flash_fwd",
    }
    spans = [("map_f", 30), ("map_b", 20), ("mix_f", 40), ("mix_r", 40),
             ("mix_b", 70), ("qkv", 150), ("ffn", 150), ("experts", 40),
             ("route", 10), ("mtp", 20), ("flash", 150), ("head", 100),
             ("while", 20), ("opt", 50), ("bare", 10)]
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


def test_the_readers_on_a_made_up_trace_and_the_sum_to_a_hundred():
    conf = _conf()
    planes, names = _planes()
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))
    d, = trace["devices"]
    ns = 1e-9
    assert d["scopes"]["hc_mix"] == {
        "forward": pytest.approx(40 * ns), "remat": pytest.approx(40 * ns),
        "backward": pytest.approx(70 * ns)}
    assert d["scopes"]["mtp_in"] == {"forward": pytest.approx(20 * ns)}
    assert d["unscoped_s"] == pytest.approx(10 * ns)
    run = {"worker": {"trace": trace, "window": {"step_metrics": {
        "moe_held_share": 0.1251, "moe_load_max_over_mean": 1.3}}},
        "conf": conf, "job": {"rows": 1, "seq": TOKENS}, "chips": 1,
        "peak": PEAK, "end_to_end": {"train_tokens_per_s": 15000.0}}
    assert _reader("hc.time_share_pct").read(run) == pytest.approx(20.0)
    assert _reader("hc.map_ms").read(run) == pytest.approx(50e-6)
    assert _reader("hc.mix_ms").read(run) == pytest.approx(150e-6)
    assert _reader("mtp.in_pct").read(run) == pytest.approx(2.0)
    assert _reader("moe.held_rows_share").read(run) == 0.1251
    assert _reader("moe.load_max_over_mean").read(run) == 1.3
    roofline = _reader("hc.mix_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * (33705426944 / 819e9) / (200 * ns))
    # a step whose maps and mixing took 80 ms reads under the ceiling
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["scopes"]["hc_mix"] = {"forward": 0.080}
    slow["devices"][0]["scopes"]["hc_map"] = {}
    got = roofline.read(dict(run, worker=dict(run["worker"], trace=slow)))
    assert got == pytest.approx(100 * 0.041154 / 0.080, rel=1e-3) and got < 75
    experts = _reader("moe.experts_roofline")
    assert experts.read(run) == pytest.approx(
        100 * flops_xing4.experts_step_flops(conf, 1, TOKENS) / 197e12
        / (40 * ns))
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * flops_xing4.flash_step_flops(conf, 1, TOKENS) / 197e12
        / (150 * ns))
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 15000.0 * flops_xing4.train_flops_per_token(conf, TOKENS)
        / 197e12)
    shares = [_reader(m).read(run) for m in (
        "step.ffn_pct", "step.attn_proj_pct", "step.attention_pct",
        "step.head_loss_pct", "step.optimizer_pct", "step.scan_pct",
        "step.unscoped_pct", "moe.time_share_pct", "hc.time_share_pct",
        "mtp.in_pct")]
    assert sum(shares) == pytest.approx(90.0)    # 900 of each 1000 ns busy
    # a model of one stream, or a parent without the scopes: nothing to read
    bare = json.loads(json.dumps(trace))
    for scope in ("hc_map", "hc_mix", "mtp_in"):
        bare["devices"][0]["scopes"].pop(scope)
    none = dict(run, worker={"trace": bare, "window": {"step_metrics": {}}})
    for metric in ("hc.time_share_pct", "hc.map_ms", "hc.mix_ms",
                   "hc.mix_roofline", "mtp.in_pct", "moe.held_rows_share"):
        assert _reader(metric).read(none) is None, metric
    untraced = dict(run, worker={"trace": None, "window": {}})
    for metric in ("hc.time_share_pct", "hc.map_ms", "hc.mix_ms",
                   "hc.mix_roofline", "mtp.in_pct", "moe.held_rows_share"):
        assert _reader(metric).read(untraced) is None, metric
