"""The yardstick of the Phi-4-mini-flash-reasoning cell: ``JAX_PLATFORMS=cpu
python -m pytest benchmark/tests/test_phi4flash.py -q``.  Its cases need no
chip, no train loop and no compile: ``tests/test_yardstick.py`` collects
them in tier-1 by name.  Entries and cells are found BY NAME and lists held
by MEMBERSHIP, so that a later cell of the same mixers appends itself to
this cell's entries without an edit here."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_phi4flash
from benchmark.loops import train
from benchmark.reference import phi4flash

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "phi-4-mini-flash-reasoning-1of8"
CELL = "phi4flash-train-s16384"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["s6.time_share_pct", "s6.scan_ms", "s6.conv_ms", "s6.kernel_ms",
           "s6.scan_roofline", "gmu.time_share_pct", "diffattn.combine_ms",
           "diffattn.combine_pct", "diffattn.window_ms", "diffattn.full_ms",
           "diffattn.roofline"]
LAYERS = {"s6": "selective-scan mixer", "gmu": "gated memory unit",
          "diffattn": "differential attention"}
CUT = {"num_hidden_layers": (32, 8), "vocab_size": (200064, 25008)}
SEQ = 16384
CAUSAL = SEQ * (SEQ + 1) // 2
WINDOW = 512 * 513 // 2 + (SEQ - 512) * 512


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


def test_the_file_is_the_catalog_row_cut_to_the_rule_at_depth_eight():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "vocabulary"]
    assert conf["share"] == {"chips_per_layer": 8, "vocabulary_over": 8,
                             "how": conf["share"]["how"]}
    assert "WITHOUT the other chips' rows" in conf["deployment"]
    why = conf["reduced"]["num_hidden_layers"]["why"]
    assert "NOT the first eight published layers" in why
    assert "M W M W | M F | G C" in why and "further pipeline stages" in why
    # every width, both head counts, the window, the rule's period, the eps
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "sliding_window", "mb_per_layer",
                "layer_norm_eps", "max_position_embeddings",
                "tie_word_embeddings", "mlp_bias", "lm_head_bias",
                "embd_pdrop", "resid_pdrop", "hidden_act", "model_type"):
        assert conf[key] == published[key], key
    # what the public file does not settle is explained, a key each
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "attention_bias", "norm_type", "position_embedding_type",
            "pairing", "window", "lambda_init", "initializer", "param_dtype",
            "dtype", "optimizer", "data"} <= set(conf["assumed"])
    for key in ("mamba_d_state", "attention_bias", "norm_type"):
        assert "modeling_phi4flash.py" in conf["assumed"][key]["why"], key
    assert "can tell another pairing apart" in conf[
        "assumed"]["pairing"]["why"]
    assert "0.356, 0.556, 0.666, 0.727" in conf[
        "assumed"]["lambda_init"]["value"]
    assert conf["scopes"] == ["s6_in", "s6_conv", "s6_scan", "s6_out", "gmu",
                              "attn_diff"]
    assert conf["kernels"] == ["selscan_", "causal_conv_"]
    assert not any(s.startswith(tuple(conf["kernels"]))
                   for s in conf["scopes"])
    cfg = train.program_config(conf)
    assert [m for m, _ in cfg.layer_kinds] == [
        "mamba1", "diff_sliding", "mamba1", "diff_sliding", "mamba1",
        "diff_full", "gmu", "diff_cross"]
    assert {f for _, f in cfg.layer_kinds} == {"dense"}
    assert phi4flash.kinds(conf["num_hidden_layers"]) == "MWMWMFGC"
    for depth in (4, 8, 12, 32):    # the FLOP module's copy of the rule
        assert flops_phi4flash.kinds(depth) == phi4flash.kinds(depth)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps, cfg.sliding_window,
            cfg.tie_embeddings, cfg.norm_type, cfg.attn_bias) == (
                2560, 40, 20, 64, 10240, 25008, 1e-5, 512, True, "layernorm",
                True)
    assert (cfg.s6_inner, cfg.s6_state, cfg.s6_conv, cfg.s6_rank) == (
        5120, 16, 4, 160)
    kw = phi4flash.layer_kwargs(conf)
    assert kw == dict(depth=8, heads=40, kv_heads=20, window=512, state=16,
                      eps=1e-5)
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(vocab_size=12504), "under an eighth of the vocabulary"),
    (dict(hidden_size=1280), "hidden_size: differs"),
    (dict(intermediate_size=5120), "intermediate_size: differs"),
    (dict(num_attention_heads=20), "num_attention_heads: differs"),
    (dict(num_key_value_heads=10), "num_key_value_heads: differs"),
    (dict(sliding_window=256), "sliding_window: differs"),
    (dict(mb_per_layer=4), "mb_per_layer: differs"),
    (dict(mamba_d_state=8), "mamba_d_state: assumed states another value"),
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
        NAME, "train-share-1x16384", 1)
    assert len(cell["why"]) <= 200 and "L = 8" in cell["why"] \
        and "not a prefix" in cell["why"] and "an eighth" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, SEQ, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the eleven entries this cell brings stand behind what was there, in
    # order, each on its mixer's layer; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS
    assert first > names.index("dsa.selected_share")
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        assert entry == {
            "name": name, "unit": entry["unit"],
            "better": "higher" if name.endswith("roofline") else "lower",
            "source": "device_trace", "layer": LAYERS[name.split(".")[0]],
            "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert {per_layer[n]["unit"] for n in METRICS if n.endswith("_ms")} == {
        "ms"}
    assert {per_layer[n]["unit"] for n in METRICS
            if n.endswith(("roofline", "_pct"))} == {"%"}
    # appended to NO older list: no experts, no flash.dq_ms, no
    # flash.window_*, no ssm.*, no rope.kernel_ms
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS)
    # one chip: the four-chip cells are as many as they were
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    assert len(bench["workloads"]) >= 17
    assert set(phi4flash.STEP_METRICS) == {
        "s6_state_absmax", "diff_lambda", "attn_window_executed_share",
        "attn_window_masked_tile_share"}
    assert all(want is None for _, want in phi4flash.STEP_METRICS.values())


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 915311616),
                                         (True, 3852562944)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (915.3 M:
    ISSUE 74's count) and of the published model (3852.6 M: the published
    "3.8B", which bears out the Mamba block's assumed sizes)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_phi4flash.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_needed_pairs_the_projections_and_the_scan_apart():
    """Hand counts at the published widths: 2097 M needed operations a token
    forward (ISSUE 74's table), of which the MLPs 60 % and the full and the
    cross layer's pairs 12 %; the scan's elementwise work stands apart."""
    conf, count = _conf(), flops_phi4flash
    assert flops.of(conf) is count and not flops.counts_experts(conf)
    d, e, m = 2560, 5120, 10240
    mamba = d * 2 * e + e * (160 + 32) + 160 * e + e * d
    attention, cross = 2 * d * d + 2 * d * 1280, 2 * d * d
    unit, mlp = 2 * d * e, 3 * d * m
    assert (mamba, attention, cross, unit, mlp) == (
        41123840, 19660800, 13107200, 26214400, 78643200)
    assert (count.mamba_matmul_params(conf),
            count.attention_matmul_params(conf),
            count.attention_matmul_params(conf, True),
            count.unit_matmul_params(conf)) == (mamba, attention, cross, unit)
    assert [count.layers(conf, k) for k in "MWFGC"] == [3, 2, 1, 1, 1]
    matmul = (3 * mamba + 3 * attention + cross + unit + 8 * mlp
              + d * 25008)
    assert count.matmul_params(conf) == matmul
    # a pair forward: in each of 20 q pairs two 64-wide scores and two
    # 128-wide value products
    assert count.pair_flops(conf) == 20 * (2 * 2 * 64 + 2 * 2 * 128) == 15360
    assert count.needed_pairs(conf, SEQ, False) == 2 * CAUSAL
    assert count.needed_pairs(conf, SEQ, True) == 2 * WINDOW
    assert WINDOW / CAUSAL == pytest.approx(0.0615, abs=5e-4)
    flash = 3 * 15360 * (2 * CAUSAL + 2 * WINDOW)
    assert count.flash_step_flops(conf, 1, SEQ) == flash \
        == pytest.approx(13.13e12, rel=1e-3)
    per_token = count.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * matmul + flash / SEQ
    forward = per_token / 3
    assert forward == pytest.approx(2097e6, rel=1e-3)
    assert 2 * 8 * mlp / forward == pytest.approx(0.60, abs=0.005)
    assert 15360 * 2 * CAUSAL / SEQ / forward == pytest.approx(0.12,
                                                                abs=0.005)
    # compute-bound: 66.6 ms of operations against 1.9 of bytes
    q, kv = SEQ * 40 * 64 * 2, SEQ * 20 * 64 * 2
    assert count.flash_step_bytes(conf, 1, SEQ) == 4 * (6 * q + 6 * kv)
    assert flops.roofline_seconds(flash, count.flash_step_bytes(
        conf, 1, SEQ), PEAK) == {"seconds": flash / 197e12,
                                 "bound": "compute"}
    # the scan: 99 elementwise operations a channel and token forward, three
    # times that a step, NOT in the model's FLOPs; memory-bound by the two
    # peaks the benchmark knows (the vector unit is neither)
    scan = 3 * 3 * SEQ * e * (6 * 16 + 3)
    assert count.selscan_step_flops(conf, 1, SEQ) == scan
    assert scan / (per_token * SEQ) == pytest.approx(0.00072, rel=0.05)
    x, dt, bc = SEQ * e * 2, SEQ * e * 4, 2 * SEQ * 16 * 2
    assert count.selscan_step_bytes(conf, 1, SEQ) == 3 * (
        (2 * x + dt + bc) + (3 * x + 2 * dt + 2 * bc))
    assert flops.roofline_seconds(scan, count.selscan_step_bytes(
        conf, 1, SEQ), PEAK)["bound"] == "memory"
    # at the published depth: nine Mamba layers, seven units, seven cross
    whole = _whole(conf)
    assert [count.layers(whole, k) for k in "MWFGC"] == [9, 8, 1, 7, 7]


def _trace(kernels=True, scopes=True):
    """A hand-made reduced trace of four steps of 1000 ms: 40 ms under
    ``s6_scan`` of which the kernels 30, 10 under ``s6_conv``, 5 each under
    ``s6_in`` and ``s6_out``, 8 under ``gmu``, 4 under ``attn_diff``; the
    flash kernels 100 ms without a window and 20 with."""
    ms = 1e-3
    named = {"s6_in": {"forward": 3 * ms, "backward": 2 * ms},
             "s6_conv": {"forward": 3 * ms, "remat": 3 * ms,
                         "backward": 4 * ms},
             "s6_scan": {"forward": 12 * ms, "backward": 28 * ms},
             "s6_out": {"forward": 2 * ms, "backward": 3 * ms},
             "gmu": {"forward": 3 * ms, "remat": 1 * ms, "backward": 4 * ms},
             "attn_diff": {"forward": 1 * ms, "remat": 1 * ms,
                           "backward": 2 * ms}}
    flash = {"flash_fwd": 30 * ms, "flash_dkv": 70 * ms,
             "flash_fwd_win": 6 * ms, "flash_dkv_win": 14 * ms}
    scan = {"selscan_fwd": 8 * ms, "selscan_bwd": 22 * ms,
            "causal_conv_fwd": 1 * ms}
    device = {
        "steps": 4, "step_s": [1.0] * 4, "window_s": 4.0, "busy_s": 4.0,
        "idle_s": 0.0, "gap_s": [], "flash_s": 4 * 120 * ms,
        "scopes": {"attention": {"forward": 36 * ms, "backward": 84 * ms},
                   "ffn": {"forward": 0.2, "backward": 0.4},
                   **(named if scopes else {})},
        "kernels": {**flash, **(scan if kernels else {})},
        "unscoped_s": 0.0}
    return {"devices": [device]}


def _run(trace, conf):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": SEQ}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 16384.0}}


def test_the_readers_and_the_flop_module_import_no_jax():
    """The driver's process reads them and fails a run if JAX is
    imported."""
    import subprocess
    import sys

    code = ("import sys, importlib.util, os\n"
            "from benchmark import flops_phi4flash\n"
            "for m in %r:\n"
            "    spec = importlib.util.spec_from_file_location('_m', "
            "os.path.join(%r, 'layer_metrics', m + '.py'))\n"
            "    mod = importlib.util.module_from_spec(spec)\n"
            "    spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'jax'\n" % (METRICS, BENCH))
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(BENCH))


def test_the_eleven_readers_on_a_made_up_run():
    conf = _conf()
    run = _run(_trace(), conf)
    read = lambda name: _reader(name).read(run)  # noqa: E731
    assert read("s6.time_share_pct") == pytest.approx(6.0)     # 60 of 1000
    assert read("s6.scan_ms") == pytest.approx(40.0)
    assert read("s6.conv_ms") == pytest.approx(10.0)
    assert read("s6.kernel_ms") == pytest.approx(30.0)
    assert read("gmu.time_share_pct") == pytest.approx(0.8)
    assert read("diffattn.combine_ms") == pytest.approx(4.0)
    assert read("diffattn.combine_pct") == pytest.approx(0.4)
    assert read("diffattn.window_ms") == pytest.approx(20.0)
    assert read("diffattn.full_ms") == pytest.approx(100.0)
    roofline = _reader("s6.scan_roofline")
    assert roofline.bound(run) == "memory"
    least = flops_phi4flash.selscan_step_bytes(conf, 1, SEQ) / 819e9
    assert least == pytest.approx(6.77e-3, rel=1e-2)
    assert roofline.read(run) == pytest.approx(100 * least / 40e-3)
    needed = flops_phi4flash.flash_step_flops(conf, 1, SEQ) / 197e12
    assert read("diffattn.roofline") == pytest.approx(100 * needed / 0.120)
    # the list-free readers of the flash kernels hold these calls too
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * needed / 0.120)
    assert _reader("flash.fwd_ms").read(run) == pytest.approx(36.0)
    for name in METRICS:
        if name.endswith("roofline"):
            assert 0.0 < read(name) <= 100.0, name


def test_on_a_program_without_the_mixers_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all; a
    program without the scopes or the kernels, an untraced run, a
    configuration whose FLOP module counts neither scan nor pairs: None each
    time, and nothing raises.  Where the XLA form of the scan runs the
    scopes have time and ``s6.kernel_ms`` alone is left out."""
    conf = _conf()
    bare = _run(_trace(kernels=False, scopes=False), conf)
    for metric in METRICS:
        if not metric.startswith("diffattn.") or "combine" in metric:
            assert _reader(metric).read(bare) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    xla = _run(_trace(kernels=False), conf)
    assert _reader("s6.kernel_ms").read(xla) is None
    assert _reader("s6.scan_ms").read(xla) == pytest.approx(40.0)
    assert _reader("s6.scan_roofline").read(xla) is not None
    mellum = _load("configs", "mellum2-12b-a2.5b-1of4.json")
    there = _run(_trace(), mellum)
    for metric in ("s6.scan_roofline", "diffattn.window_ms",
                   "diffattn.full_ms", "diffattn.roofline"):
        assert _reader(metric).read(there) is None, metric
