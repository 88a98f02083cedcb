"""The yardstick of the Nemotron-3-Super cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_nemotron3.py -q``.  Its cases need no chip, no
train loop and no compile: ``tests/test_yardstick.py`` collects them in
tier-1 by name.  Entries and cells are found BY NAME and lists held by
MEMBERSHIP (never by a list's end), so that a later cell appends itself to
this cell's entries without an edit here."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_nemotron3, trace_reduce
from benchmark.loops import train
from benchmark.reference import nemotron3
from benchmark.tests.test_nemotron_h import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron3-super-120b-a12b-1of64"
CELL = "nemotron3super-train-s4096"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["lmoe.latent_pct", "lmoe.latent_roofline", "lmoe.route_ms",
           "ssm_wide.kernel_ms", "ssm_wide.scan_roofline"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share",
               "ssm.time_share_pct", "ssm.scan_ms", "ssm.conv_ms",
               "ssm.out_ms", "mtp.in_pct"]
CUT = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 8),
       "vocab_size": (131072, 16384)}
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


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


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_sixty_four():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "experts_held", "vocabulary"]
    # the three published keys this model is chosen for stay verbatim, as
    # does the pattern, whose first 11 characters are the published 5:5:1
    assert conf["hybrid_override_pattern"] == PATTERN == published[
        "hybrid_override_pattern"] and len(PATTERN) == 88
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        40, 40, 8)
    assert nemotron3.kinds(conf) == tuple("MEMEMEM*EME")
    assert (conf["moe_latent_size"], conf["num_experts_per_tok"],
            conf["mtp_hybrid_override_pattern"],
            conf["num_nextn_predict_layers"]) == (1024, 22, "*E", 1)
    assert conf["share"] == {
        "chips_per_layer": 64, "vocabulary_over": 8, "leading_dense": None,
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    assert "LAST stage" in conf["deployment"]   # where the module lies
    # no width, head count or routing number changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "moe_latent_size", "moe_shared_expert_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size", "expand",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "norm_topk_prob", "n_group",
                "topk_group", "layer_norm_epsilon", "mlp_hidden_act",
                "rescale_prenorm_residual", "tie_word_embeddings"):
        assert conf[key] == published[key], key
    # what the public file does not settle is explained, a key each
    assert {"latent", "routed_scaling", "mtp_module", "mtp_loss_coef",
            "first_expert", "scoring_func", "topk_method", "topk_norm_eps",
            "bias_update_speed", "router_aux_loss_coef",
            "selection_bias_init_std", "position_embedding_type",
            "in_proj_order", "gated_norm", "initializer", "param_dtype",
            "dtype", "optimizer", "data"} <= set(conf["assumed"])
    assert "w_latent_out" in conf["assumed"]["initializer"]["value"]
    assert conf["scopes"] == ["ssm_in", "ssm_conv", "ssm_scan", "ssm_out",
                              "moe_latent", "mtp_in"]
    assert conf["kernels"] == ["ssd_"]
    assert (conf["reference"], conf["flops"]) == ("nemotron3",
                                                  "flops_nemotron3")
    # all of it is READ by the program
    cfg = train.program_config(conf)
    assert cfg.layer_kinds == tuple(
        {"M": ("mamba", "none"), "E": ("none", "moe"),
         "*": ("attention", "none")}[c] for c in "MEMEMEM*EME")
    assert cfg.mtp_runs == ((("attention", "none"), 1), (("none", "moe"), 1))
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.shared_width, cfg.moe_latent, cfg.vocab_size,
            cfg.norm_eps, cfg.tie_embeddings, cfg.ffn_act) == (
                4096, 32, 2, 128, 2688, 5376, 1024, 16384, 1e-5, False,
                "relu2")
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_experts, cfg.routed_scaling_factor,
            cfg.aux_loss_coef, cfg.topk_norm_eps, cfg.select_bias_init) == (
                512, 8, 0, 22, True, "sigmoid", True, 1, 5, 0.0, 1e-20,
                0.005)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_inner, cfg.ssm_groups,
            cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk,
            cfg.position_embedding) == (128, 64, 8192, 8, 128, 4, 128, "nope")
    assert (cfg.num_nextn, cfg.mtp_pattern, cfg.mtp_loss_coef,
            cfg.rescale_prenorm_residual, cfg.published_layers) == (
                1, "*E", 0.1, True, 88)
    assert cfg.residual_init_scale == 176 ** -0.5
    kw = nemotron3.layer_kwargs(conf)
    assert (kw["kinds"], kw["mtp_kinds"], kw["k"], kw["factor"], kw["first"],
            kw["ssm_heads"], kw["groups"], kw["heads"], kw["kv_heads"]) == (
                tuple("MEMEMEM*EME"), ("*", "E"), 22, 5.0, 0, 128, 8, 32, 2)
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200
    check = conf["check"]
    assert 0.0 < check["token_nll_rms"] < 0.1 and "int8" in check["why"]


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(n_routed_experts=4), "4 experts held; a share keeps at least 8"),
    (dict(n_routed_experts=16),
     "run 16 x chips_per_layer 64 is not the published"),
    (dict(vocab_size=8192), "under an eighth of the vocabulary"),
    (dict(moe_latent_size=512), "moe_latent_size: differs"),
    (dict(moe_intermediate_size=1344), "moe_intermediate_size: differs"),
    (dict(hidden_size=2048), "hidden_size: differs"),
    (dict(mamba_num_heads=64), "mamba_num_heads: differs"),
    (dict(num_experts_per_tok=8), "num_experts_per_tok: differs"),
    (dict(mtp_hybrid_override_pattern="E"),
     "mtp_hybrid_override_pattern: differs"),
    (dict(hybrid_override_pattern="MEMEMEM*EME"),
     "hybrid_override_pattern: differs"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_each_floor_and_each_width_violated_in_turn(fault, said):
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    for key, value in fault.items():
        conf[key] = value
        if key in conf["reduced"]:
            conf["reduced"][key]["run"] = value
    faults = cuts.complaints(conf, published)
    assert any(said in f for f in faults), faults


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-share-1x4096", 1)
    assert len(cell["why"]) <= 200
    assert "176" in cell["why"] and "11264" in cell["why"]
    assert 4096 * 22 / 512 == 176 and 64 * 176 == 11264
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, 4096, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the five entries this cell brings stand in order behind what was
    # there; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + 5] == METRICS
    assert first > names.index("kda_neg.beta_max")
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        assert entry == {
            "name": name, "unit": entry["unit"],
            "better": "higher" if "roofline" in name else "lower",
            "source": "device_trace",
            "layer": per_layer[
                "ssm.scan_ms" if name.startswith("ssm_wide")
                else "moe.dispatch_ms"]["layer"],
            "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert [per_layer[m]["unit"] for m in METRICS] == ["%", "%", "ms", "ms",
                                                       "%"]
    # the sibling's entries of the same two quantities, under their names,
    # keep their one cell
    for ours, theirs in (("ssm_wide.kernel_ms", "ssm.kernel_ms"),
                         ("ssm_wide.scan_roofline",
                          "ssm.groups_scan_roofline")):
        assert per_layer[ours]["unit"] == per_layer[theirs]["unit"]
        assert per_layer[theirs]["workloads"] == ["nemotronh-train-s8192"]
    for name in APPENDED_TO:    # appended: behind every cell that was there
        assert CELL in per_layer[name]["workloads"][1:]
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS + APPENDED_TO)
    # one chip: the four-chip cells are as many as they were
    assert sum(c["chips"] == 4 for c in bench["workloads"]) * 4 <= len(
        bench["workloads"])
    assert nemotron3.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "moe_rows_visited_share"} <= set(nemotron3.STEP_METRICS)
    assert nemotron3.loss_rtol(4096) == nemotron3.LOSS_RTOL


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1378724736),
                                         (True, 123611033088)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (1378.7 M:
    ISSUE 66's count) and of the published model (123.6 B with the module
    and its own 512 experts, 2.94 B; 120.7 B without it: the name's
    120B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_nemotron3.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_latent_the_module_and_both_heads():
    """Hand counts at the published widths: 28.8 TFLOP a step needed (7.03
    GFLOP a token forward and backward); the five Mamba layers' projections
    3.29 of the 7.03, the two attention layers 0.43 + 0.20, the six expert
    layers 1.69 (shared 0.264 each, latent pair 0.050, router 0.0126,
    routed 0.0114), the module's projection 0.20, the two heads 0.81."""
    conf = _conf()
    count = flops_nemotron3
    assert flops.of(conf) is count and flops.counts_experts(conf)
    # the stack's layers AND the module's
    assert (count.layers(conf, "M"), count.layers(conf, "E"),
            count.layers(conf, "*"), count.modules(conf)) == (5, 6, 2, 1)
    mamba = 4096 * (8192 + 8192 + 2 * 8 * 128 + 128) + 8192 * 4096
    attention = 4096 * 128 * (2 * 32 + 2 * 2)
    expert, pair, shared = 2 * 1024 * 2688, 2 * 4096 * 1024, 2 * 4096 * 5376
    assert (mamba, attention, expert, pair, shared) == (
        109576192, 35651584, 5505024, 8388608, 44040192)
    assert (count.mamba_params(conf), count.attention_params(conf),
            count.expert_params(conf), count.latent_params(conf),
            count.shared_params(conf)) == (mamba, attention, expert, pair,
                                           shared)
    assert count.held_per_token(conf) == 22 * 8 / 512 == 0.34375
    layer = 4096 * 512 + shared + pair + 0.34375 * expert
    matmul = (5 * mamba + 2 * attention + 6 * layer + 2 * 4096 * 4096
              + 2 * 4096 * 16384)
    assert count.active_matmul_params(conf) == matmul
    flash = 6 * 2 * 4096 * 32 * 128 * 4096
    assert count.flash_step_flops(conf, 1, 4096) == flash \
        == pytest.approx(0.825e12, rel=1e-3)
    pairs = 128 * 129 // 2
    scan = 3 * 5 * (2 * pairs * (8 * 128 + 8192) + 4 * 128 * 128 * 8192) / 128
    assert count.ssd_flops_per_token(conf) == scan
    per_token = count.train_flops_per_token(conf, 4096)
    assert per_token == 6 * matmul + flash / 4096 + scan
    assert per_token == pytest.approx(7.035e9, rel=1e-3)
    assert per_token * 4096 == pytest.approx(28.81e12, rel=1e-3)
    # the grouped products at the LATENT width, the rows HELD: 1408 of
    # 90112 a layer, 176 an expert where the slice's sees 11264
    assert count.experts_step_flops(conf, 1, 4096) == \
        6 * 4096 * 6 * 0.34375 * expert == pytest.approx(0.279e12, rel=1e-3)
    rows, weights = 6 * 1408 * (1024 + 2688) * 2, 3 * 8 * expert * 2
    assert count.experts_step_bytes(conf, 1, 4096) == 6 * (rows + weights)
    # bound by the 11 MB an expert's matrices weigh, three passes: 2.40 ms
    # of bytes against 1.42 ms of operations
    assert flops.roofline_seconds(
        count.experts_step_flops(conf, 1, 4096),
        count.experts_step_bytes(conf, 1, 4096), PEAK)["bound"] == "memory"
    # the latent pair: every token, six layers, three passes; by operations
    assert count.latent_step_flops(conf, 1, 4096) == 6 * 4096 * 6 * pair \
        == pytest.approx(1.237e12, rel=1e-3)
    one_pass = (4096 * (4096 + 1024) + 4096 * 1024) * 2
    assert count.latent_step_bytes(conf, 1, 4096) == 6 * 2 * 3 * one_pass
    assert flops.roofline_seconds(
        count.latent_step_flops(conf, 1, 4096),
        count.latent_step_bytes(conf, 1, 4096), PEAK) == {
            "seconds": 6 * 4096 * 6 * pair / 197e12, "bound": "compute"}
    # the scans: 5 layers x 128 heads, B and C at 8 groups; by bytes
    x, bc, dt = 4096 * 8192 * 2, 2 * 4096 * 8 * 128 * 2, 4096 * 128 * 4
    assert count.ssd_step_bytes(conf, 1, 4096) == 5 * (5 * x + 3 * (bc + dt))
    assert flops.roofline_seconds(
        count.ssd_step_flops(conf, 1, 4096),
        count.ssd_step_bytes(conf, 1, 4096), PEAK)["bound"] == "memory"
    # TWO attention layers' k and v at the 2 KV heads
    q, kv = 4096 * 32 * 128 * 2, 4096 * 2 * 128 * 2
    assert count.flash_step_bytes(conf, 1, 4096) == 2 * 6 * (q + kv)
    # a model without the latent or the module counts as the sibling's
    plain = {k: v for k, v in conf.items() if k not in (
        "moe_latent_size", "num_nextn_predict_layers",
        "mtp_hybrid_override_pattern")}
    assert (count.latent(plain), count.latent_params(plain),
            count.modules(plain), count.layers(plain, "E")) == (4096, 0, 0, 5)
    assert count.latent_step_flops(plain, 1, 4096) == 0
    # at the published depth and experts: 40 : 40 : 8 + the module, 22 a
    # token
    whole = _whole(conf)
    assert (count.layers(whole, "M"), count.layers(whole, "E"),
            count.layers(whole, "*"), count.held_per_token(whole)) == (
                40, 41, 9, 22.0)


def _run(trace, conf):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": 4096}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 10000.0}}


def _latent_planes(**kw):
    """``test_nemotron_h.py``'s synthetic planes (900 ns of ops a step:
    the scan 180 of them — 100 in the ``ssd_*`` kernels unless
    ``kernel_names=()`` —, ``moe_route`` 20) with the 80 ns under ``ffn``
    moved under ``moe_latent`` and the 30 under ``attn_qkv`` under
    ``mtp_in``."""
    planes, names = _planes(**kw)
    moved = {"/ffn/": "/moe_latent/", "/attn_qkv/": "/mtp_in/"}

    def move(stack):
        for old, new in moved.items():
            stack = stack.replace(old, new)
        return stack

    return planes, {plane: {event: move(stack)
                            for event, stack in events.items()}
                    for plane, events in names.items()}


def _trace(conf, planes_and_names, **kw):
    planes, names = planes_and_names
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=kw.get("scopes", conf.get("scopes", ())),
        kernels=conf.get("kernels", ()))


def test_the_five_readers_on_synthetic_planes():
    conf, ns = _conf(), 1e-9
    run = _run(_trace(conf, _latent_planes()), conf)
    assert _reader("lmoe.latent_pct").read(run) == pytest.approx(8.0)
    assert _reader("lmoe.route_ms").read(run) == pytest.approx(20e-6)
    latent = _reader("lmoe.latent_roofline")
    assert latent.bound(run) == "compute"
    least = flops_nemotron3.latent_step_flops(conf, 1, 4096) / 197e12
    assert least == pytest.approx(6.28e-3, rel=1e-3)
    assert latent.read(run) == pytest.approx(100 * least / (80 * ns))
    assert _reader("ssm_wide.kernel_ms").read(run) == pytest.approx(100e-6)
    scan = _reader("ssm_wide.scan_roofline")
    assert scan.bound(run) == "memory"
    least = flops_nemotron3.ssd_step_bytes(conf, 1, 4096) / 819e9
    assert least == pytest.approx(2.394e-3, rel=1e-3)
    assert scan.read(run) == pytest.approx(100 * least / (180 * ns))
    # the sibling's readers of the same two quantities read the same
    assert _reader("ssm.kernel_ms").read(run) == _reader(
        "ssm_wide.kernel_ms").read(run)
    assert _reader("ssm.groups_scan_roofline").read(run) == scan.read(run)
    # the XLA form of the scan: 0.0, a number
    xla = _run(_trace(conf, _latent_planes(kernel_names=())), conf)
    kernel_ms = _reader("ssm_wide.kernel_ms").read(xla)
    assert kernel_ms == 0.0 and isinstance(kernel_ms, float)
    assert scan.read(xla) == pytest.approx(scan.read(run))
    # with the step's idle tenth the cell's shares make 100
    shares = [_reader(m).read(run) or 0.0 for m in (
        "lmoe.latent_pct", "mtp.in_pct", "ssm.time_share_pct",
        "moe.time_share_pct", "step.ffn_pct", "step.attn_proj_pct",
        "step.attention_pct", "step.head_loss_pct", "step.optimizer_pct",
        "step.scan_pct", "step.unscoped_pct")]
    assert sum(shares) == pytest.approx(90.0)
    assert _reader("mtp.in_pct").read(run) == pytest.approx(3.0)
    # the appended lists' readers read this cell's planes
    assert _reader("ssm.out_ms").read(run) == pytest.approx(50e-6)
    assert _reader("moe.experts_xla_ms").read(run) == 0.0
    experts = _reader("moe.experts_roofline")
    assert experts.bound(run) == "memory"
    assert experts.read(run) == pytest.approx(
        100 * (flops_nemotron3.experts_step_bytes(conf, 1, 4096) / 819e9)
        / (90 * ns))


def test_on_a_program_without_the_latent_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all
    (``LlamaConfig`` has no ``moe_latent``: a ``TypeError`` at once); a
    program without the scope ``moe_latent`` or the ``ssm_*`` ones, an
    untraced run, a configuration whose FLOP module counts no latent pair:
    None each time, and nothing raises."""
    conf = _conf()
    planes, names = _planes()
    bare = {plane: {event: stack.replace("ssm_", "xyz_").replace(
        "moe_route", "xyz_route") for event, stack in events.items()}
        for plane, events in names.items()}
    without = _run(_trace(conf, (planes, bare)), conf)
    for metric in METRICS:
        assert _reader(metric).read(without) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    # the sibling's configuration: its module counts no latent pair, and
    # its program opens no such scope
    sibling = _load("configs", "nemotron-twotower-30b-a3b-1of8.json")
    there = _run(_trace(sibling, _latent_planes(), scopes=conf["scopes"]),
                 sibling)
    assert _reader("lmoe.latent_roofline").read(there) is None
    assert _reader("lmoe.latent_roofline").bound(there) is None
    plain = _run(_trace(sibling, _planes()), sibling)
    assert _reader("lmoe.latent_pct").read(plain) is None
    mistral = _load("configs", "mistral-7b-v0.1-d4.json")
    assert _reader("ssm_wide.scan_roofline").read(
        _run(_trace(conf, _latent_planes()), mistral)) is None
