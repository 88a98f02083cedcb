"""The yardstick of the JoyAI-LLM-Flash cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_joyai_flash.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import pytest

from benchmark import cuts, flops, flops_joyai, trace_reduce
from benchmark.loops import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "joyai-llm-flash-1of2-x4"
CELL = "joyai-train-s4096-ep4"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9}
D, HEADS, ROWS, SEQ = 2048, 32, 4, 4096
TOKENS = ROWS * SEQ


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


def test_the_file_is_the_catalog_row_cut_to_one_hosts_share_of_two():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (v["published"], v["run"], v["kind"])
            for k, v in conf["reduced"].items()} == {
        "num_hidden_layers": (40, 5, "depth"),
        "n_routed_experts": (256, 128, "experts_held"),
        "vocab_size": (129280, 64640, "vocabulary")}
    assert conf["share"]["chips_per_layer"] == 2      # it counts HOSTS
    assert "HOSTS" in conf["share"]["how"]
    assert conf["share"]["leading_dense"] == "first_k_dense_replace"
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "head_dim",
                "num_attention_heads", "n_shared_experts",
                "num_experts_per_tok", "num_nextn_predict_layers",
                "rope_scaling", "rope_theta", "routed_scaling_factor",
                "first_k_dense_replace"):
        assert conf[key] == published[key], key
    assert conf["scopes"] == ["moe_exchange", "mtp_in"]
    assert "kernels" not in conf       # flash_ and moe_ are trace_scopes' own
    assert conf["ep_ranks"] == 4 == conf["assumed"]["ep_ranks"]["value"]
    # a cut that the rule refuses: a width, or a share that does not add up
    assert cuts.complaints(dict(conf, moe_intermediate_size=512), published)
    assert cuts.complaints(dict(conf, share=dict(
        conf["share"], chips_per_layer=8)), published)


def test_the_program_is_told_both_counts_and_every_published_number():
    fields = train.program_fields(_conf())
    assert (fields["num_experts"], fields["experts_held"],
            fields["first_expert"], fields["num_selected"]) == (256, 128, 0, 8)
    assert (fields["vocab_size"], fields["num_layers"],
            fields["leading_dense"]) == (64640, 5, 1)
    assert (fields["embed_dim"], fields["mlp_dim"], fields["dense_mlp_dim"],
            fields["num_heads"]) == (2048, 768, 7168, 32)
    assert (fields["q_lora_rank"], fields["kv_lora_rank"],
            fields["qk_nope_dim"], fields["qk_rope_dim"],
            fields["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert fields["rope_scaling"] is None and fields["rope_theta"] == 32e6
    assert (fields["router_scoring"], fields["topk_method"],
            fields["routed_scaling_factor"], fields["norm_topk_prob"],
            fields["shared_experts"]) == ("sigmoid", "noaux_tc", 2.5, True, 1)
    assert (fields["num_nextn"], fields["mtp_loss_coef"],
            fields["bias_update_speed"], fields["aux_loss_coef"]) == (
                1, 0.3, 0.001, 0.0)
    cfg = train.program_config(_conf())
    assert cfg.kind_runs == ((("latent", "dense"), 1), (("latent", "moe"), 4))
    assert cfg.mtp_runs == ((("latent", "moe"), 1),)
    assert cfg.latent_qk_dim == 192 and cfg.local_experts == 128
    assert cfg.hc_mult == 1                      # the plain residual


def test_the_cell_its_job_and_its_metrics():
    bench = _load("..", "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-4x4096-ep4", 4)
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["check_rows"],
            job["warmup_steps"], job["traced_steps"], job["mesh"]) == (
                "train", 4, 4096, 4, 2, 4, {"ep": 4})
    ours = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert ours == ["moe.exchange_ms", "moe.exchange_roofline",
                    "moe.rank_rows_max_over_mean"]
    for name in ("moe.experts_roofline", "moe.load_max_over_mean",
                 "moe.held_rows_share", "moe.rows_visited_share",
                 "moe.token_rows_read_share", "mtp.in_pct"):
        metric, = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL in metric["workloads"], name
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    # this cell took the second four-chip place: of the 9 cells there were
    # then, 2 are a quarter, rounded down (the rule since:
    # test_benchmark.py::test_at_most_a_quarter_of_the_cells_take_four_chips)
    assert [c["name"] for c in bench["workloads"]].index(CELL) == 8
    four = [c["name"] for c in bench["workloads"] if c["chips"] == 4]
    assert four[:2] == ["deepseek7b-train-s4096-x4", CELL]
    config, = [c for c in bench["configs"] if c["name"] == NAME]
    assert config["reduced"] == list(_conf()["reduced"])


def test_flops_joyai_against_hand_counts():
    """ISSUE 44's table: what the HOST computes a token, a chip's experts
    and a chip's exchange."""
    conf = _conf()
    assert flops.of(conf) is flops_joyai and flops.counts_experts(conf)
    attention = (D * 1536 + 1536 * HEADS * 192 + D * 576
                 + 512 * HEADS * 256 + HEADS * 128 * D)
    assert flops_joyai.attention_params(conf) == attention == 26345472
    expert = 3 * D * 768
    assert flops_joyai.expert_params(conf) == expert == 4718592
    assert (flops_joyai.blocks(conf), flops_joyai.expert_layers(conf),
            flops_joyai.published_experts(conf)) == (6, 5, 256)
    assert flops_joyai.held_per_token(conf) == 4      # of a token's 8
    active = (6 * attention + 3 * D * 7168
              + 5 * (D * 256 + expert + 4 * expert)
              + 2 * D * 64640 + 2 * D * D)
    assert flops_joyai.active_matmul_params(conf) == active
    assert flops_joyai.total_params(conf) == pytest.approx(3521e6, rel=5e-4)
    causal = 3 * 6 * SEQ * HEADS * (192 + 128)
    per_token = flops_joyai.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * active + causal
    assert per_token / 3 == pytest.approx(1443e6, rel=1e-3)
    assert per_token * TOKENS == pytest.approx(70.9e12, rel=1e-3)
    # the two heads over the slice: 37 % of it at depth 5
    assert 6 * 2 * D * 64640 / per_token == pytest.approx(0.367, abs=2e-3)
    # the kernels' own counts: attention the host's, the experts ONE chip's
    assert flops_joyai.flash_step_flops(conf, ROWS, SEQ) == causal * TOKENS
    assert flops_joyai.flash_step_bytes(conf, ROWS, SEQ) == 6 * 3 * TOKENS * (
        HEADS * 192 + HEADS * 128 + 64 + 2 * HEADS * 128) * 2
    assert flops_joyai.experts_step_flops(conf, ROWS, SEQ) == \
        6 * TOKENS * 5 * 4 * expert / 4
    assert flops_joyai.experts_step_bytes(conf, ROWS, SEQ) == 5 * (
        9 * TOKENS * 4 * (D + 768) * 2 + 3 * 128 * expert * 2) / 4
    # the uncut layer would count eight experts a token
    whole = dict(conf, n_routed_experts=256, reduced={})
    assert flops_joyai.held_per_token(whole) == 8


def test_exchange_step_bytes_is_from_shapes_and_not_from_the_programs_form():
    conf = _conf()
    # a token's 8 distinct choices of 256 miss a rank's 32 with C(224, 8) /
    # C(256, 8)
    missed = 1.0
    for i in range(8):
        missed *= (224 - i) / (256 - i)
    share = flops_joyai.needs_rank_share(conf)
    assert share == pytest.approx(1 - missed)
    assert share == pytest.approx(0.6618, abs=1e-4)
    # 3 other ranks' 4096 tokens each, the share of them that needs this
    # rank, 2048 bf16 numbers: in and back, forward and backward, 5 layers
    one_way = 3 * 4096 * share * D * 2
    assert flops_joyai.exchange_step_bytes(conf, ROWS, SEQ) == \
        pytest.approx(5 * 4 * one_way)
    # under what the program's form moves: its all-gather brings EVERY
    # token of the other ranks (the issue's 50 MB a direction a layer a
    # pass), so the count is the share of that
    gathered = 5 * 4 * 3 * 4096 * D * 2
    assert gathered / 20 == pytest.approx(50.3e6, rel=1e-3)
    assert flops_joyai.exchange_step_bytes(conf, ROWS, SEQ) == \
        pytest.approx(share * gathered)
    # the module reads nothing of the program and nothing of the job but
    # its shapes: twice the tokens, twice the bytes; one rank, none
    assert flops_joyai.exchange_step_bytes(conf, 2 * ROWS, SEQ) == \
        pytest.approx(2 * 5 * 4 * one_way)
    assert flops_joyai.exchange_step_bytes(
        dict(conf, ep_ranks=1), ROWS, SEQ) == 0.0
    with open(flops_joyai.__file__) as f:
        assert "ray_tpu" not in f.read().replace("``ray_tpu", "")


def _planes():
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops under the scopes this model opens, on two chips:
    the second waits 40 ns longer in the exchange."""
    fusion = ('%fusion.{i} = bf16[4096,2048]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[4096,2048]{{1,0}} %p.{i}), kind=kLoop')
    keys = ("qkv", "ffn", "experts", "route", "dispatch", "mtp", "head",
            "while", "opt", "bare")
    texts = {k: fusion.format(i=i) for i, k in enumerate(keys)}
    texts["gather"] = (
        '%all-gather.7 = bf16[16384,2048]{1,0:T(8,128)(2,1)} all-gather('
        'bf16[4096,2048]{1,0} %fusion.50), dimensions={0}')
    texts["scatter"] = (
        '%reduce-scatter.9 = bf16[4096,2048]{1,0:T(8,128)(2,1)} '
        'reduce-scatter(bf16[16384,2048]{1,0} %fusion.51), dimensions={0}')
    texts["scatter_b"] = texts["gather"].replace("all-gather.7",
                                                 "all-gather.8")
    texts["grads"] = (
        '%all-reduce.3 = bf16[2048,7168]{1,0:T(8,128)(2,1)} all-reduce('
        'bf16[2048,7168]{1,0} %fusion.52), to_apply=%add')
    texts["flash"] = (
        '%closed_call.3 = (bf16[1,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, '
        'f32[1,32,4096,128]{3,2,1,0:T(8,128)}) custom-call(bf16[1,32,4096,'
        '192]{3,2,1,0} %fusion.99), custom_call_target="tpu_custom_call"')
    body = "jit(step)/jvp(while)/body/checkpoint/"
    back = "jit(step)/transpose(jvp(while))/body/"
    region = "jit(moe_block)/shard_map/"
    stacks = {
        "qkv": body + "attn_qkv/dot_general",
        "ffn": body + "ffn/dot_general",
        "experts": body + region + "moe_experts/moe_gmm",
        "route": body + region + "moe_route/dot_general",
        "dispatch": body + region + "moe_dispatch/sort",
        "gather": body + region + "moe_exchange/all_gather",
        "scatter": body + region + "moe_exchange/reduce_scatter",
        "scatter_b": back + region
        + "transpose(jvp(moe_exchange))/all_gather",
        "grads": back + "transpose(jvp(ffn))/dot_general",
        "mtp": "jit(step)/jvp(mtp_in)/dot_general",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
        "flash": body + "attention/flash_fwd",
    }

    def chip(wait):
        spans = [("qkv", 150), ("flash", 150), ("ffn", 100), ("route", 10),
                 ("gather", 30), ("dispatch", 30), ("experts", 80),
                 ("scatter", 40 + wait), ("scatter_b", 30), ("grads", 50),
                 ("mtp", 20), ("head", 100 - wait), ("while", 20),
                 ("opt", 80), ("bare", 10)]
        ops, mods = [], []
        for i in range(3):
            start = 1000 * i
            mods.append((f"jit_step({i})", start, start + 1000))
            for key, ns in spans:
                ops.append((texts[key], start, start + ns))
                start += ns
        return {"XLA Ops": ops, "XLA Modules": mods}

    names = {texts[k]: stacks[k] for k in texts}
    planes = {"/device:TPU:0": chip(0), "/device:TPU:1": chip(40),
              "/host:CPU": {"python": []}}
    return planes, {"/device:TPU:0": names, "/device:TPU:1": names}


def test_the_readers_on_a_made_up_trace_and_the_sum_to_a_hundred():
    conf = _conf()
    planes, names = _planes()
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))
    ns = 1e-9
    first, second = trace["devices"]
    assert first["scopes"]["moe_exchange"] == {
        "forward": pytest.approx(70 * ns), "backward": pytest.approx(30 * ns)}
    assert second["scopes"]["moe_exchange"]["forward"] == pytest.approx(
        110 * ns)
    assert first["collectives_per_step"] == 4
    assert first["collective_s"] == pytest.approx(2 * 150 * ns)
    run = {"worker": {"trace": trace, "window": {"step_metrics": {
        "moe_held_share": 0.4987, "moe_load_max_over_mean": 1.9,
        "moe_rank_rows_max_over_mean": 1.034}}},
        "conf": conf, "job": {"rows": ROWS, "seq": SEQ}, "chips": 4,
        "peak": PEAK, "end_to_end": {"train_tokens_per_s": 60000.0}}
    # both chips' steps take as long: every reader of scopes reads the first
    assert _reader("moe.exchange_ms").read(run) == pytest.approx(100e-6)
    assert _reader("moe.exchange_roofline").read(run) == pytest.approx(
        100 * flops_joyai.exchange_step_bytes(conf, ROWS, SEQ) / 200e9
        / (100 * ns))
    assert _reader("moe.rank_rows_max_over_mean").read(run) == 1.034
    assert _reader("moe.held_rows_share").read(run) == 0.4987
    assert _reader("moe.load_max_over_mean").read(run) == 1.9
    assert _reader("mtp.in_pct").read(run) == pytest.approx(2.0)
    assert _reader("collectives.exposed_pct").read(run) == pytest.approx(
        19.0)                                    # the chip that waits
    assert _reader("moe.experts_roofline").read(run) == pytest.approx(
        100 * flops_joyai.experts_step_flops(conf, ROWS, SEQ) / 197e12
        / (80 * ns))
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * flops_joyai.flash_step_flops(conf, ROWS, SEQ) / 4 / 197e12
        / (150 * ns))
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 60000.0 * flops_joyai.train_flops_per_token(conf, SEQ)
        / (4 * 197e12))
    # an exchange that took 20 ms a step reads well under 100
    slow = json.loads(json.dumps(trace))
    for d in slow["devices"]:
        d["scopes"]["moe_exchange"] = {"forward": 0.012, "backward": 0.008}
    got = _reader("moe.exchange_roofline").read(
        dict(run, worker=dict(run["worker"], trace=slow)))
    assert got == pytest.approx(100 * 0.003331 / 0.020, rel=1e-3) and got < 100
    # the shares: the shared step.*_pct, the expert layer's four scopes,
    # the module's input and the exchange (its milliseconds over the step's)
    shares = [_reader(m).read(run) for m in (
        "step.ffn_pct", "step.attn_proj_pct", "step.attention_pct",
        "step.head_loss_pct", "step.optimizer_pct", "step.scan_pct",
        "step.unscoped_pct", "moe.time_share_pct", "mtp.in_pct")]
    exchange = 100 * _reader("moe.exchange_ms").read(run) / _reader(
        "train_step.step_ms").read(run)
    assert exchange == pytest.approx(10.0)
    assert sum(shares) + exchange == pytest.approx(90.0)  # 900 of 1000 busy
    # a program without the exchange (the parent, a cell on one chip) has
    # nothing under the scope: the two readers return nothing and raise
    # nothing, and the counter is not among its step metrics
    bare = json.loads(json.dumps(trace))
    for d in bare["devices"]:
        d["scopes"].pop("moe_exchange")
    without = dict(run, worker={"trace": bare, "window": {"step_metrics": {}}})
    for name in ("moe.exchange_ms", "moe.exchange_roofline",
                 "moe.rank_rows_max_over_mean"):
        assert _reader(name).read(without) is None, name
    untraced = dict(run, worker={"trace": None, "window": {}})
    for name in ("moe.exchange_ms", "moe.exchange_roofline",
                 "moe.rank_rows_max_over_mean"):
        assert _reader(name).read(untraced) is None, name
    # a configuration whose FLOP module has no such count
    other = dict(run, conf=_load("configs", "olmoe-1b-7b-0125-1chip.json"))
    assert _reader("moe.exchange_roofline").read(other) is None
