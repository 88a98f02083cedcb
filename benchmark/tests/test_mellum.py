"""The yardstick of the Mellum2-12B-A2.5B cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_mellum.py -q``.  Its cases need no chip, no train
loop and no compile: ``tests/test_yardstick.py`` collects them in tier-1 by
name."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_mellum, trace_reduce
from benchmark.loops import train
from benchmark.reference import mellum
from benchmark.tests.test_trinity import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mellum2-12b-a2.5b-1of4"
CELL = "mellum2-train-s16384"
TRINITY = "trinity-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["flash.window_masked_tile_share", "flash.full_ms"]
WINDOW_METRICS = ["flash.window_ms", "flash.window_roofline",
                  "flash.window_executed_share"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share"] + WINDOW_METRICS
S, F = "sliding_attention", "full_attention"
CUT = {"num_hidden_layers": (28, 4),
       "layer_types": ([S, S, S, F] * 7, [S, S, S, F]),
       "mlp_layer_types": (["sparse"] * 28, ["sparse"] * 4),
       "num_experts": (64, 16), "vocab_size": (98304, 24576)}
WINDOW_PAIRS = 1024 * 1025 // 2 + 15360 * 1024     # 16.25 M at 16384
CAUSAL_PAIRS = 16384 * 16385 // 2                  # 134.2 M


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


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_four():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "pattern", "pattern", "experts_held", "vocabulary"]
    # one whole period, no dense layer leading: 3 windowed : 1 full
    assert cuts.leading_dense(published, "mlp_layer_types") == 0
    assert cuts.period(published["layer_types"]) == 4 == len(
        conf["layer_types"])
    assert (conf["layer_types"].count(S), conf["layer_types"].count(F)) == (
        3, 1)
    assert conf["share"] == {
        "chips_per_layer": 4, "leading_dense": "mlp_layer_types",
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count, window, experts-per-token or rotary number changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "sliding_window", "num_experts_per_tok", "norm_topk_prob",
                "rms_norm_eps", "rope_parameters", "max_position_embeddings",
                "tie_word_embeddings"):
        assert conf[key] == published[key], key
    assert conf["rope_parameters"] == {
        F: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 500000}}
    # what the public file does not state is explained, a key each
    assert {"first_expert", "router_aux_loss_coef", "position_embedding_type",
            "qk_norm", "mtp_head", "intermediate_size", "rope_pairing",
            "yarn_bounds", "window", "initializer", "param_dtype", "dtype",
            "optimizer", "data"} <= set(conf["assumed"])
    assert "scopes" not in conf and "kernels" not in conf
    cfg = train.program_config(conf)
    assert cfg.kind_runs == (((S, "moe"), 3), ((F, "moe"), 1))
    assert tuple(mellum.kinds(conf)) == cfg.layer_kinds
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps, cfg.tie_embeddings,
            cfg.sliding_window) == (2304, 32, 4, 128, 896, 24576, 1e-6,
                                    False, 1024)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_experts, cfg.aux_loss_coef,
            cfg.leading_dense) == (64, 16, 0, 8, True, "softmax", False, 0,
                                   0.001, 0)
    assert (cfg.position_embedding, cfg.rotary(True), cfg.rotary(False)) == (
        "rope_by_layer_type", True, True)
    theta, scaling = cfg.rope_rule(False)
    assert dict(scaling, rope_theta=theta) == conf["rope_parameters"][F]
    assert cfg.rope_rule(True) == (500000, (("rope_type", "default"),))
    kw = mellum.layer_kwargs(conf)
    assert (kw["window"], kw["k"], kw["renormalise"], kw["first"]) == (
        1024, 8, True, 0)
    assert dict(dict(kw["groups"])[F])["factor"] == 16
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(num_experts=4), "4 experts held; a share keeps at least 8"),
    (dict(num_experts=8), "run 8 x chips_per_layer 4 is not the published"),
    (dict(vocab_size=6144), "under an eighth of the vocabulary"),
    (dict(layer_types=[S, S, F, S]), "not the first 4 entries"),
    (dict(sliding_window=4096), "sliding_window: differs from the published"),
    (dict(moe_intermediate_size=512), "moe_intermediate_size: differs"),
    (dict(rope_parameters={}), "rope_parameters: differs"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_each_floor_and_each_width_violated_in_turn(fault, said):
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    for key, value in fault.items():
        conf[key] = value
        if key in conf["reduced"]:
            conf["reduced"][key]["run"] = value
    if "num_hidden_layers" in fault:
        for key in ("layer_types", "mlp_layer_types"):
            conf[key] = conf["reduced"][key]["run"] = published[key][:3]
    faults = cuts.complaints(conf, published)
    assert any(said in f for f in faults), faults


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert len(bench["workloads"]) >= 12   # found by name: later cells pass
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-1x16384", 1)
    assert len(cell["why"]) <= 200
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, 16384, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    # the two entries this cell brings stand last of what was there, in order
    first = names.index(METRICS[0])
    assert names[first:first + 2] == METRICS
    assert first > names.index("flash.xla_ms")
    for name in METRICS:
        assert per_layer[name] == {
            "name": name, "unit": per_layer[name]["unit"], "better": "lower",
            "source": ("program_counter" if "share" in name
                       else "device_trace"),
            "layer": per_layer["flash_roofline"]["layer"],
            "moves": "train_tokens_per_s", "workloads": [TRINITY, CELL]}
    for name in APPENDED_TO:    # appended: behind every cell that was there
        assert per_layer[name]["workloads"][-1] == CELL or CELL in \
            per_layer[name]["workloads"][1:]
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(
                      METRICS + APPENDED_TO)
    # both four-chip places were taken: this one is a one-chip cell
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(CELL) + 1]
    assert sum(c["chips"] == 4 for c in upto) == 2 <= len(upto) // 4
    assert mellum.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "moe_rows_visited_share", "attn_window_executed_share",
            "attn_window_masked_tile_share"} <= set(mellum.STEP_METRICS)


def test_trinitys_window_entries_stand_as_they_were_with_this_cell_appended():
    """What ``test_trinity.py``'s case of the same name as this file's
    third holds of Trinity's own three entries, in the form that an
    appended cell leaves true (that case pins ``workloads == [its cell]``,
    which this PR was asked to append to; tier-1 collects this one in its
    place)."""
    bench = _load(os.pardir, "BENCHMARK.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(WINDOW_METRICS[0])
    assert names[first:first + 3] == WINDOW_METRICS
    for name in WINDOW_METRICS:
        assert per_layer[name] == {
            "name": name, "unit": per_layer[name]["unit"],
            "better": "higher" if "roofline" in name else "lower",
            "source": ("program_counter" if "executed" in name
                       else "device_trace"),
            "layer": per_layer["flash_roofline"]["layer"],
            "moves": "train_tokens_per_s",
            "workloads": [TRINITY] + per_layer[name]["workloads"][1:]}
        assert per_layer[name]["workloads"][:2] == [TRINITY, CELL]
    cell, = [c for c in bench["workloads"] if c["name"] == TRINITY]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-preview-1of32", "train-share-1x8192", 1)
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(TRINITY) + 1]
    assert sum(c["chips"] == 4 for c in upto) == 2 == len(upto) // 4


def test_flash_xla_ms_stands_as_it_was_before_this_cells_two_entries():
    """What ``test_flash_xla_ms.py``'s first case holds of PR 54's entry —
    ``flash.fwd_ms``'s fields under its own name, no list of cells — but
    for "the last entry": this PR's two stand behind it (tier-1 collects
    this case in that one's place)."""
    bench = _load(os.pardir, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    fwd, = [m for m in bench["per_layer"] if m["name"] == "flash.fwd_ms"]
    at = names.index("flash.xla_ms")
    assert bench["per_layer"][at] == {**fwd, "name": "flash.xla_ms"}
    assert "workloads" not in fwd
    assert names[at + 1:at + 3] == METRICS
    assert names[at - 3:at] == WINDOW_METRICS


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 595153152),
                                         (True, 12149915904)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (ISSUE 55's
    595.2 M) and of the published model (12.15 B: the name's 12B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_mellum.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_what_the_window_leaves_and_the_held_experts_compute():
    """Hand counts at the published widths: 27.8 TFLOP a step needed, the
    ONE full layer's attention 6.6 of them and the three windowed layers'
    2.4 — an eighth of what the causal pairs would be."""
    conf = _conf()
    assert flops.of(conf) is flops_mellum and flops.counts_experts(conf)
    assert (flops_mellum.windowed_layers(conf),
            flops_mellum.full_layers(conf)) == (3, 1)
    assert flops_mellum.window_pairs(conf, 16384) == WINDOW_PAIRS == 16253440
    assert flops_mellum.causal_pairs(16384) == CAUSAL_PAIRS == 134225920
    assert WINDOW_PAIRS / CAUSAL_PAIRS == pytest.approx(0.1211, abs=1e-4)
    # at the window's length and below the window IS the causal mask
    assert flops_mellum.window_pairs(conf, 1024) == 1024 * 1025 // 2
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    expert = 3 * 2304 * 896
    assert (attention, expert) == (21233664, 6193152)
    assert flops_mellum.attention_params(conf) == attention
    assert flops_mellum.held_per_token(conf) == 2.0        # 8 x 16 / 64
    matmul = 4 * (attention + 2304 * 64 + 2 * expert) + 2304 * 24576
    assert flops_mellum.active_matmul_params(conf) == matmul
    # 595.2 M: four layers of 21.23 + 0.15 + 99.09 M and two norms, the
    # embedding and the head over the slice, the last norm
    assert flops_mellum.total_params(conf) == 4 * (
        attention + 2304 * 64 + 16 * expert + 2 * 2304) \
        + 2 * 2304 * 24576 + 2304 == 595153152
    pair = 12 * 32 * 128
    assert flops_mellum.window_step_flops(conf, 1, 16384) == \
        pair * 3 * WINDOW_PAIRS
    flash = pair * (3 * WINDOW_PAIRS + CAUSAL_PAIRS)
    assert flops_mellum.flash_step_flops(conf, 1, 16384) == flash
    assert flash == pytest.approx(8.99e12, rel=1e-3)
    assert pair * 3 * WINDOW_PAIRS == pytest.approx(2.40e12, rel=2e-3)
    per_token = flops_mellum.train_flops_per_token(conf, 16384)
    assert per_token == 6 * matmul + flash / 16384
    assert per_token * 16384 == pytest.approx(27.84e12, rel=1e-3)
    # the head over the slice: a third of the products (the depth cut's doing)
    assert 6 * 2304 * 24576 / (6 * matmul) == pytest.approx(0.295, abs=5e-3)
    # had the causal pairs been counted in the windowed layers: 8.26 times
    assert CAUSAL_PAIRS / WINDOW_PAIRS == pytest.approx(8.26, abs=0.01)
    layer = 6 * 16384 * (32 + 4) * 128 * 2
    assert flops_mellum.window_step_bytes(conf, 1, 16384) == 3 * layer
    assert flops_mellum.flash_step_bytes(conf, 1, 16384) == 4 * layer
    assert flops.roofline_seconds(
        flops_mellum.window_step_flops(conf, 1, 16384),
        flops_mellum.window_step_bytes(conf, 1, 16384), PEAK) == {
            "seconds": pair * 3 * WINDOW_PAIRS / 197e12, "bound": "compute"}
    # the grouped products over the rows HELD: 32768 of 131072 a layer
    assert flops_mellum.experts_step_flops(conf, 1, 16384) == \
        6 * 16384 * 4 * 2.0 * expert
    rows, weights = 9 * 32768 * (2304 + 896) * 2, 3 * 16 * expert * 2
    assert flops_mellum.experts_step_bytes(conf, 1, 16384) == 4 * (
        rows + weights)
    # at the published depth and experts: 21 windowed : 7 full, 8 a token
    whole = _whole(conf)
    assert (flops_mellum.windowed_layers(whole),
            flops_mellum.full_layers(whole),
            flops_mellum.held_per_token(whole)) == (21, 7, 8.0)


def _run(trace, conf, seq=16384, **program_parts):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": {}},
                       "check": {"program_parts": program_parts}},
            "conf": conf, "job": {"rows": 1, "seq": seq}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 30000.0}}


def _trace(conf, win="_win"):
    planes, names = _planes(win)
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_the_two_readers_on_recorded_values():
    """``test_trinity.py``'s synthetic planes: the windowed kernels 60 + 80
    + 100 ns a step, the plain ones 20 + 30 + 30."""
    conf = _conf()
    run = _run(_trace(conf), conf, attn_window_masked_tile_share=0.4,
               attn_window_executed_share=1.25)
    assert _reader("flash.full_ms").read(run) == pytest.approx(80e-6)
    assert _reader("flash.window_ms").read(run) == pytest.approx(240e-6)
    assert _reader("flash.full_ms").read(run) + _reader(
        "flash.window_ms").read(run) == pytest.approx(sum(
            _reader(f"flash.{k}_ms").read(run) for k in ("fwd", "dq", "dkv")))
    assert _reader("flash.window_masked_tile_share").read(run) == 0.4
    # the accepted windowed roofline through this cell's FLOP module
    least = 12 * 32 * 128 * 3 * WINDOW_PAIRS / 197e12
    assert _reader("flash.window_roofline").read(run) == pytest.approx(
        100 * least / 240e-9)
    # Trinity's run through the same readers
    trinity = _load("configs", "trinity-large-preview-1of32.json")
    run = _run(_trace(trinity), trinity, seq=8192,
               attn_window_masked_tile_share=0.12)
    assert _reader("flash.full_ms").read(run) == pytest.approx(80e-6)
    assert _reader("flash.window_masked_tile_share").read(run) == 0.12


def test_on_a_program_without_them_the_two_readers_return_nothing():
    """The parent's program reports no ``attn_window_masked_tile_share``;
    a trace whose flash kernels are all windowed, or that names none, has no
    full layer's time; an untraced run, and a worker that reported no
    check, likewise: None, and nothing raises."""
    conf = _conf()
    share, full = (_reader(m) for m in METRICS)
    assert share.read(_run(_trace(conf), conf)) is None
    assert share.read({"worker": {}}) is None
    assert full.read(_run(None, conf)) is None
    only_windowed = _trace(conf)
    for d in only_windowed["devices"]:
        d["kernels"] = {k: v for k, v in d["kernels"].items()
                        if k.endswith("_win")}
    assert full.read(_run(only_windowed, conf)) is None
    # a program without the window: every flash kernel is a plain one
    assert full.read(_run(_trace(conf, win=""), conf)) == pytest.approx(
        320e-6)
