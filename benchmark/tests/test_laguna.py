"""The yardstick of the Laguna-XS.2 cell: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_laguna.py -q``.  Its cases need no chip, no train loop
and no compile: ``tests/test_yardstick.py`` collects them in tier-1 by name.
Entries and cells are found BY NAME and lists held by MEMBERSHIP, so that a
later cell appends itself to the entries this one joined without an edit
here.  The cell brings NO per-layer entry (the 128 are full): it joins the
lists of twelve that were there."""

import functools
import json
import os
import sys

import jax
import pytest

from benchmark import cuts, flops, flops_laguna, trace_reduce
from benchmark.loops import train
from benchmark.reference import laguna
from benchmark.tests.test_trinity import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "laguna-xs.2-33b-a3b-1of8"
CELL = "laguna-train-s16384"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WINDOW_METRICS = ["flash.window_ms", "flash.window_roofline",
                  "flash.window_executed_share",
                  "flash.window_masked_tile_share", "flash.full_ms"]
EXPERT_METRICS = ["moe.experts_roofline", "moe.load_max_over_mean",
                  "moe.rows_visited_share", "moe.token_rows_read_share",
                  "moe.experts_xla_ms", "moe.held_rows_share"]
JOINED = WINDOW_METRICS + EXPERT_METRICS + ["rope.kernel_ms"]
S, F = "sliding_attention", "full_attention"
CUT = {"num_hidden_layers": (40, 8),
       "layer_types": ([F, S, S, S] * 10, [F, S, S, S] * 2),
       "num_attention_heads_per_layer": ([48, 64, 64, 64] * 10,
                                         [48, 64, 64, 64] * 2),
       "mlp_layer_types": (["dense"] + ["sparse"] * 39,
                           ["dense"] + ["sparse"] * 7),
       "num_experts": (256, 32), "vocab_size": (100352, 12544)}
SEQ = 16384
WINDOW_PAIRS = 512 * 513 // 2 + (SEQ - 512) * 512      # 8.26 M at 16384
CAUSAL_PAIRS = SEQ * (SEQ + 1) // 2                     # 134.2 M


def _load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def _conf():
    return _load("configs", NAME + ".json")


def _reader(metric):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def before_this_cell(case):
    """``case`` of an older cell's file run on ``BENCHMARK.json`` as it was
    before this cell JOINED the lists of the twelve entries above: three
    older cases hold an entry's ``workloads`` as a closed list (Mellum2's
    two, ``rope.kernel_ms``'s three), which an appended name opens; their
    files are the benchmark's and are not this PR's to edit, so
    ``tests/test_yardstick.py`` collects them through here, and
    ``test_the_cell_its_job_and_its_metrics`` below holds what they no
    longer see: this cell's name is the LAST of each list, behind every
    name that was there, and no other field moved."""
    module = sys.modules[case.__module__]

    class _Json:
        """The module's ``json`` with this cell out of what it loads."""

        def __getattr__(self, name):
            return getattr(json, name)

        @staticmethod
        def load(f):
            data = json.load(f)
            if isinstance(data, dict) and "per_layer" in data:
                for m in data["per_layer"]:
                    if CELL in m.get("workloads", ()):
                        m["workloads"].remove(CELL)
            return data

    @functools.wraps(case)
    def run():
        real, module.json = module.json, _Json()
        try:
            case()
        finally:
            module.json = real

    return run


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "pattern", "pattern", "pattern", "experts_held",
        "vocabulary"]
    # ONE dense layer leads, kept once; the kinds come f s s s: two whole
    # periods, 2 full : 6 sliding, the published 10 : 30
    assert cuts.leading_dense(published, "mlp_layer_types") == 1
    assert cuts.period(published["layer_types"]) == 4
    assert cuts.period(published["num_attention_heads_per_layer"]) == 4
    assert (conf["layer_types"].count(F), conf["layer_types"].count(S)) == (
        2, 6)
    assert conf["share"] == {
        "chips_per_layer": 8, "leading_dense": "mlp_layer_types",
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count (by kind), KV heads, window, rotary group, router
    # width, experts a token or shared expert changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "sliding_window", "num_experts_per_tok", "rope_parameters",
                "partial_rotary_factor", "moe_routed_scaling_factor",
                "rms_norm_eps", "max_position_embeddings", "gating",
                "moe_apply_router_weight_on_input", "tie_word_embeddings"):
        assert conf[key] == published[key], key
    assert sorted(set(zip(conf["layer_types"],
                          conf["num_attention_heads_per_layer"]))) == [
        (F, 48), (S, 64)]
    assert conf["reduced"]["num_experts"]["published"] == 256
    assert conf["rope_parameters"][F]["partial_rotary_factor"] == 0.5
    # what the public file does not state is explained, a key each
    assert {"attn_output_gate", "num_shared_experts", "score_func",
            "topk_method", "norm_topk_prob", "bias_update_speed",
            "selection_bias_init_std", "router_aux_loss_coef",
            "position_embedding_type", "yarn_over_the_rotary_width",
            "rope_pairing", "qk_norm", "norms", "first_expert",
            "param_dtype", "dtype", "optimizer", "data"} <= set(
                conf["assumed"])
    assert conf["scopes"] == ["attn_head_gate", "rope_partial"]
    assert "kernels" not in conf
    cfg = train.program_config(conf)
    assert cfg.kind_runs == (((F, "dense"), 1), ((S, "moe"), 3),
                             ((F, "moe"), 1), ((S, "moe"), 3))
    assert tuple(laguna.kinds(conf)) == cfg.layer_kinds
    assert (cfg.q_heads(False), cfg.q_heads(True), cfg.num_kv_heads,
            cfg.head_dim, cfg.rotary_dim(False), cfg.rotary_dim(True),
            cfg.sliding_window, cfg.attn_output_gate) == (
                48, 64, 8, 128, 64, 128, 512, "per_head")
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_width, cfg.routed_scaling_factor,
            cfg.aux_loss_coef) == (256, 32, 0, 8, True, "sigmoid", True,
                                   512, 2.5, 0.0)
    kw = laguna.layer_kwargs(conf)
    assert (kw["window"], kw["k"], kw["scale"], kw["first"],
            dict(kw["heads"])) == (512, 8, 2.5, 0, {F: 48, S: 64})
    assert dict(dict(kw["groups"])[F])["factor"] == 64
    assert set(dict(kw["groups"])) == {F, S}    # the number beside them: no
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=4), "3 layers after the 1 leading dense"),
    (dict(num_experts=4), "4 experts held; a share keeps at least 8"),
    (dict(num_experts=16), "run 16 x chips_per_layer 8 is not the published"),
    (dict(vocab_size=6272), "under an eighth of the vocabulary"),
    (dict(layer_types=[F, S, S, F, F, S, S, S]), "not the first 8 entries"),
    (dict(num_attention_heads_per_layer=[48] * 8), "not the first 8 entries"),
    (dict(num_attention_heads=64), "num_attention_heads: differs"),
    (dict(num_key_value_heads=4), "num_key_value_heads: differs"),
    (dict(sliding_window=511), "sliding_window: differs from the published"),
    (dict(moe_intermediate_size=768), "moe_intermediate_size: differs"),
    (dict(num_experts_per_tok=4), "num_experts_per_tok: differs"),
    (dict(rope_parameters={}), "rope_parameters: differs"),
    (dict(partial_rotary_factor=1.0), "partial_rotary_factor: differs"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_each_floor_each_width_and_each_head_count_violated_in_turn(fault,
                                                                    said):
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    for key, value in fault.items():
        conf[key] = value
        if key in conf["reduced"]:
            conf["reduced"][key]["run"] = value
    if "num_hidden_layers" in fault:
        for key in ("layer_types", "num_attention_heads_per_layer",
                    "mlp_layer_types"):
            conf[key] = conf["reduced"][key]["run"] = published[key][:4]
    faults = cuts.complaints(conf, published)
    assert any(said in f for f in faults), faults


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-share-1x16384", 1)
    assert len(cell["why"]) <= 200 and "512 rows" in cell["why"] \
        and "4096" in cell["why"] and "3.9" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, SEQ, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # NO entry of this cell's own: the 128 were full
    assert len(bench["per_layer"]) == 128
    # it JOINED twelve lists, behind every name that was there ...
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(JOINED)
    for name in JOINED:
        assert per_layer[name]["workloads"].count(CELL) == 1
        assert per_layer[name]["workloads"].index(CELL) >= {
            "rope.kernel_ms": 3}.get(name, 2)
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # ... the windowed kernels' behind Trinity's and Mellum2's cells, the
    # rotation's behind the three dense and OLMoE cells
    for name in WINDOW_METRICS:
        assert per_layer[name]["workloads"][:2] == [
            "trinity-train-s8192", "mellum2-train-s16384"]
    assert per_layer["rope.kernel_ms"]["workloads"][:3] == [
        "mistral7b-train-s4096", "mistral7b-train-s512", "olmoe-train-s4096"]
    # not ``flash.dq_ms``: no side of any cell reports it since PR 69
    assert CELL not in per_layer["flash.dq_ms"]["workloads"]
    # and it reports every entry without a list (``train_step.mfu_pct``,
    # ``flash_roofline``, ``step.scan_pct`` among them)
    for name in ("train_step.mfu_pct", "flash_roofline", "step.scan_pct",
                 "device.peak_hbm_gb"):
        assert "workloads" not in per_layer[name]
    # one chip: twenty cells, nineteen configurations, two on four chips
    assert (len(bench["workloads"]), len(bench["configs"])) >= (20, 19)
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(CELL) + 1]
    assert sum(c["chips"] == 4 for c in upto) == 2 <= len(upto) // 4
    assert laguna.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"attn_q_heads_full", "attn_q_heads_window",
            "attn_rotary_width_full", "attn_window_keys", "moe_held_share",
            "moe_load_max_over_mean", "moe_rows_visited_share",
            "attn_window_executed_share",
            "attn_window_masked_tile_share"} <= set(laguna.STEP_METRICS)


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1118277376),
                                         (True, 33442606848)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (ISSUE 83's
    "about 1119 M") and of the published model (33.44 B: the name's 33.4B,
    which a gate a head AND channel would make 34.07 B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_laguna.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total
    else:
        wide_gate = 2048 * 127 * (10 * 48 + 30 * 64)
        assert (total + wide_gate) / 1e9 == pytest.approx(34.07, abs=5e-3)


def test_flops_count_attention_by_the_layers_kind():
    """Hand counts at the published widths: 64.96 TFLOP a step needed,
    forward and backward — the projections AT THE KIND'S HEADS 28.13 of
    them, the two full layers' pairs under 48 heads 19.79, the six windows'
    under 64 heads 4.87 — so the mixers whose shapes follow the kind are
    four fifths of the step."""
    conf = _conf()
    assert flops.of(conf) is flops_laguna and flops.counts_experts(conf)
    assert (flops_laguna.heads(conf, F), flops_laguna.heads(conf, S)) == (
        48, 64)
    assert (flops_laguna.full_layers(conf),
            flops_laguna.windowed_layers(conf),
            flops_laguna.dense_layers(conf),
            flops_laguna.expert_layers(conf)) == (2, 6, 1, 7)
    assert flops_laguna.window_pairs(conf, SEQ) == WINDOW_PAIRS == 8257792
    assert flops_laguna.causal_pairs(SEQ) == CAUSAL_PAIRS == 134225920
    assert WINDOW_PAIRS / CAUSAL_PAIRS == pytest.approx(0.0615, abs=1e-4)
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    assert (full, sliding) == (29458432, 37879808)
    assert flops_laguna.attention_params(conf, F) == full
    assert flops_laguna.attention_params(conf, S) == sliding
    assert flops_laguna.mixer_params(conf) == 2 * full + 6 * sliding
    expert, dense = 3 * 2048 * 512, 3 * 2048 * 8192
    assert flops_laguna.held_per_token(conf) == 1.0         # 8 x 32 / 256
    matmul = (2 * full + 6 * sliding + dense
              + 7 * (2048 * 256 + expert + 1.0 * expert) + 2048 * 12544)
    assert flops_laguna.active_matmul_params(conf) == matmul
    assert flops_laguna.total_params(conf) == (
        2 * full + 6 * sliding + dense
        + 7 * (2048 * 256 + 256 + 33 * expert) + 8 * 2 * 2048
        + 2 * 2048 * 12544 + 2048) == 1118277376
    pair_f, pair_s = 12 * 48 * 128, 12 * 64 * 128
    assert flops_laguna.window_step_flops(conf, 1, SEQ) == \
        pair_s * 6 * WINDOW_PAIRS
    flash = pair_s * 6 * WINDOW_PAIRS + pair_f * 2 * CAUSAL_PAIRS
    assert flops_laguna.flash_step_flops(conf, 1, SEQ) == flash
    assert pair_f * 2 * CAUSAL_PAIRS == pytest.approx(19.79e12, rel=1e-3)
    assert pair_s * 6 * WINDOW_PAIRS == pytest.approx(4.87e12, rel=1e-3)
    per_token = flops_laguna.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * matmul + flash / SEQ
    step = per_token * SEQ
    assert step == pytest.approx(64.96e12, rel=1e-3)
    # ISSUE 83's shares of the needed operations: projections 43 %, the
    # full layers' pairs 31 %, the windows' 7.5 %, the head 3.9 %
    assert 6 * (2 * full + 6 * sliding) * SEQ / step == pytest.approx(
        0.433, abs=2e-3)
    assert pair_f * 2 * CAUSAL_PAIRS / step == pytest.approx(0.305, abs=2e-3)
    assert pair_s * 6 * WINDOW_PAIRS / step == pytest.approx(0.075, abs=2e-3)
    assert 6 * 2048 * 12544 * SEQ / step == pytest.approx(0.039, abs=1e-3)
    # ONE head count for both kinds would misread either kernel's roofline:
    # 48 in the windows a quarter low, 64 in the full layers a third high
    assert pair_f / pair_s == 0.75
    # had the causal pairs been counted under the window: sixteen times
    assert CAUSAL_PAIRS / WINDOW_PAIRS == pytest.approx(16.25, abs=0.01)
    layer = lambda h: 6 * SEQ * (h + 8) * 128 * 2      # noqa: E731
    assert flops_laguna.window_step_bytes(conf, 1, SEQ) == 6 * layer(64)
    assert flops_laguna.flash_step_bytes(conf, 1, SEQ) == \
        6 * layer(64) + 2 * layer(48)
    assert flops.roofline_seconds(
        flops_laguna.window_step_flops(conf, 1, SEQ),
        flops_laguna.window_step_bytes(conf, 1, SEQ), PEAK) == {
            "seconds": pair_s * 6 * WINDOW_PAIRS / 197e12, "bound": "compute"}
    # the grouped products over the rows HELD: 16384 of 131072 a layer, 512
    # rows an expert — against 32 experts' matrices the BYTES bound them
    assert flops_laguna.experts_step_flops(conf, 1, SEQ) == \
        6 * SEQ * 7 * 1.0 * expert
    rows, weights = 9 * SEQ * (2048 + 512) * 2, 3 * 32 * expert * 2
    assert flops_laguna.experts_step_bytes(conf, 1, SEQ) == 7 * (
        rows + weights)
    assert flops.roofline_seconds(
        flops_laguna.experts_step_flops(conf, 1, SEQ),
        flops_laguna.experts_step_bytes(conf, 1, SEQ), PEAK)["bound"] == \
        "memory"
    # at the published depth and experts: 10 full : 30 sliding, 8 a token
    whole = _whole(conf)
    assert (flops_laguna.full_layers(whole),
            flops_laguna.windowed_layers(whole),
            flops_laguna.expert_layers(whole),
            flops_laguna.held_per_token(whole)) == (10, 30, 39, 8.0)
    # a file that gives one kind two counts has no count for the kind
    with pytest.raises(ValueError, match="one head count a kind"):
        flops_laguna.heads(dict(conf, num_attention_heads_per_layer=[
            48, 64, 64, 48, 48, 64, 64, 64]), S)


def _run(trace, conf, **program_parts):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": program_parts},
                       "check": {"program_parts": program_parts}},
            "conf": conf, "job": {"rows": 1, "seq": SEQ}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 20000.0}}


def _trace(conf, win="_win"):
    planes, names = _planes(win)
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_the_joined_readers_count_by_kind_through_this_cells_module():
    """``test_trinity.py``'s synthetic planes (the windowed kernels 60 + 80
    + 100 ns a step, the plain ones 20 + 30 + 30) through the readers this
    cell joined: the windowed roofline over SIXTY-FOUR heads and the
    window's pairs, the whole roofline over both kinds, the share of the
    peak — none of which may pass 100 % on a real trace unless a count is
    wrong."""
    conf = _conf()
    run = _run(_trace(conf), conf, attn_window_masked_tile_share=0.9,
               attn_window_executed_share=2.0)
    assert _reader("flash.full_ms").read(run) == pytest.approx(80e-6)
    assert _reader("flash.window_ms").read(run) == pytest.approx(240e-6)
    assert _reader("flash.window_masked_tile_share").read(run) == 0.9
    assert _reader("flash.window_executed_share").read(run) == 2.0
    least = 12 * 64 * 128 * 6 * WINDOW_PAIRS / 197e12
    assert _reader("flash.window_roofline").read(run) == pytest.approx(
        100 * least / 240e-9)
    whole = least + 12 * 48 * 128 * 2 * CAUSAL_PAIRS / 197e12
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * whole / 320e-9)
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 20000.0 * flops_laguna.train_flops_per_token(conf, SEQ)
        / 197e12)
    # 20 k tokens/s is 40 % of the peak: what ISSUE 83 reckoned a step by
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        40.25, abs=0.05)
    # a parent's program has no window kernel under this name: nothing
    assert _reader("flash.window_ms").read(
        _run(_trace(conf, win=""), conf)) is None
    assert _reader("flash.window_roofline").read(_run(None, conf)) is None


def test_the_readers_and_the_flop_module_import_no_jax():
    """The driver's process reads them and fails a run if JAX is
    imported."""
    import subprocess

    code = ("import sys, importlib.util, os\n"
            "from benchmark import flops_laguna\n"
            "for m in %r:\n"
            "    spec = importlib.util.spec_from_file_location('_m', "
            "os.path.join(%r, 'layer_metrics', m + '.py'))\n"
            "    mod = importlib.util.module_from_spec(spec)\n"
            "    spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'jax'\n" % (JOINED, BENCH))
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(BENCH))
