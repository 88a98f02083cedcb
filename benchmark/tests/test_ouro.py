"""The yardstick of the Ouro looped-stack cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_ouro.py -q``.  Its cases need no chip, no train
loop and no compile: ``tests/test_yardstick.py`` collects them in tier-1 by
name.  Entries and cells are found BY NAME and lists held by MEMBERSHIP, so
that a later cell of a looped model appends itself to this cell's entries
without an edit here."""

import importlib.util
import json
import math
import os

import jax
import pytest

from benchmark import cuts, flops, flops_ouro
from benchmark.loops import train
from benchmark.reference import ouro_looped

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "ouro-2.6b-d8"
CELL = "ouro-train-ut4-s4096"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# five, not ISSUE 79's six: BENCHMARK.json may hold 128 per-layer metrics
# and held 123; ``ut.exit_ms`` (the scope in ms: ``ut.exit_pct`` x
# ``train_step.step_ms``) is the one left out
METRICS = ["ut.exit_pct", "ut.head_loss_ms", "ut.head_loss_roofline",
           "ut.expected_steps", "ut.exit_entropy"]
ENTRIES = {"ut.exit_pct": ("%", "lower", "device_trace"),
           "ut.head_loss_ms": ("ms", "lower", "device_trace"),
           "ut.head_loss_roofline": ("%", "higher", "device_trace"),
           "ut.expected_steps": ("passes", "lower", "program_counter"),
           "ut.exit_entropy": ("nats", "higher", "program_counter")}
ROWS, SEQ, PASSES, DEPTH = 2, 4096, 4, 8


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


def test_the_file_is_the_catalog_row_cut_in_depth_alone():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k for k in published if conf[k] != published[k]} == {
        "num_hidden_layers", "layer_types"} == set(conf["reduced"])
    assert (published["num_hidden_layers"], conf["num_hidden_layers"]) == (
        48, DEPTH)
    assert conf["layer_types"] == published["layer_types"][:DEPTH] \
        == ["full_attention"] * DEPTH
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "pattern"]
    assert "share" not in conf
    # every width and the passes as published
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["vocab_size"],
            conf["total_ut_steps"], conf["early_exit_threshold"]) == (
                2048, 16, 16, 128, 5632, 49152, PASSES, 1)
    assert "six pipeline stages of 8" in conf["deployment"] \
        and "no code standing in" in conf["deployment"]
    # the program's group carries the published passes and the assumed beta
    assert conf["looped"] == conf["assumed"]["looped"]["value"] == {
        "passes": conf["total_ut_steps"], "entropy_coef": 0.05}
    assert all(len(a["why"]) > 20 for a in conf["assumed"].values())
    # every value the public file does not carry names what settles it
    for key in ("block_norm", "looped", "final_norm_every_pass", "exit_gate",
                "exit_distribution", "objective", "early_exit",
                "no_projection_biases", "rope"):
        assert "settle" in conf["assumed"][key]["why"], key
    cfg = train.program_config(conf)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.num_layers, cfg.rope_theta,
            cfg.norm_eps, cfg.block_norm, cfg.post_norm_init,
            cfg.tie_embeddings) == (
                2048, 16, 16, 128, 5632, 49152, DEPTH, 1000000, 1e-6,
                "sandwich", 1.0, False)
    assert cfg.passes == PASSES \
        and cfg.loop_group["entropy_coef"] == 0.05
    assert cfg.layer_kinds == (("full_attention", "dense"),) * DEPTH
    assert cfg.sliding_window == 0      # none of the three window keys maps
    assert ouro_looped.layer_kwargs(conf) == dict(
        heads=16, kv_heads=16, theta=1e6, eps=1e-6)
    assert conf["scopes"] == ["ut_exit"] and "kernels" not in conf
    assert (conf["reference"], conf["flops"]) == ("ouro_looped", "flops_ouro")
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"])
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(hidden_size=1024), "hidden_size: differs"),
    (dict(intermediate_size=2816), "intermediate_size: differs"),
    (dict(num_attention_heads=8), "num_attention_heads: differs"),
    (dict(total_ut_steps=2), "total_ut_steps: differs"),
    (dict(layer_types=["full_attention"] * 4), "not the first 8 entries"),
    (dict(looped={"passes": 2, "entropy_coef": 0.05}),
     "looped: assumed states another value"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_each_width_and_the_passes_changed_in_turn_is_a_complaint(fault,
                                                                  said):
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
        NAME, "train-2x4096", 1)
    assert len(cell["why"]) <= 200 and "4 passes" in cell["why"] \
        and "17 %" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", ROWS, SEQ, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the entries this cell brings stand behind what was there, in order,
    # on one layer; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS
    assert first > names.index("bd.masked_share")
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        unit, better, source = ENTRIES[name]
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": source, "layer": "looped stack",
                         "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # appended to NO list that was there: the model has no expert layer,
    # no window, no kernel of its own
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS)
    assert len(bench["per_layer"]) <= 128
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    assert len(bench["workloads"]) >= 19
    held = {k: want for k, (_, want) in ouro_looped.STEP_METRICS.items()
            if want is not None}
    assert held == {"ut_steps": float(PASSES)}
    assert {"ut_exit_entropy", "ut_expected_steps"} <= set(
        ouro_looped.STEP_METRICS)


def test_the_parameter_count_is_init_params():
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated): 612.4 M, ISSUE 79's count,
    each shared tensor ONCE."""
    from ray_tpu.models.llama import init_params

    conf = _conf()
    assert flops_ouro.total_params(conf) == 612438017
    shapes = jax.eval_shape(
        lambda k: init_params(k, train.program_config(conf)),
        jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 612438017
    assert shapes["exit_gate"].shape == (2048,) \
        and shapes["exit_gate_bias"].shape == ()


def test_flops_count_a_layer_and_the_head_once_a_pass():
    """Hand counts at the published widths: 32 layer applications and four
    heads a token, the needed causal pairs at 4096, the gate three times."""
    conf, count = _conf(), flops_ouro
    assert flops.of(conf) is count and not flops.counts_experts(conf)
    d = 2048
    attention, mlp, head = 4 * d * d, 3 * d * 5632, d * 49152
    assert (attention, mlp, head) == (16777216, 34603008, 100663296)
    assert count.layer_matmul_params(conf) == attention + mlp
    pairs = 6 * DEPTH * SEQ * 16 * 128      # flops.py's count, once through
    assert flops.attention_flops_per_token(conf, SEQ) == pairs
    per_token = count.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * (PASSES * DEPTH * (attention + mlp)
                             + PASSES * head + 3 * d) + PASSES * pairs
    assert per_token == pytest.approx(13.89e9, rel=1e-3)
    # the four heads are 17 % of the step's needed operations at depth 8
    assert 6 * PASSES * head / per_token == pytest.approx(0.174, abs=0.002)
    assert count.head_step_flops(conf, ROWS * SEQ) == \
        6 * PASSES * ROWS * SEQ * head == pytest.approx(1.979e13, rel=1e-3)
    assert count.flash_step_flops(conf, ROWS, SEQ) == \
        PASSES * pairs * ROWS * SEQ
    q = ROWS * SEQ * 16 * 128 * 2
    assert count.flash_step_bytes(conf, ROWS, SEQ) == \
        PASSES * DEPTH * 12 * q
    assert flops.roofline_seconds(
        count.flash_step_flops(conf, ROWS, SEQ),
        count.flash_step_bytes(conf, ROWS, SEQ), PEAK)["bound"] == "compute"


def _trace(scopes=True):
    """A hand-made reduced trace of four steps of 900 ms: 160 ms under the
    heads' two scopes (20 of them the logits made again), 3 ms under
    ``ut_exit``."""
    ms = 1e-3
    device = {
        "steps": 4, "step_s": [0.9] * 4, "window_s": 3.6, "busy_s": 3.6,
        "idle_s": 0.0, "gap_s": [], "flash_s": 4 * 0.2,
        "scopes": {"ffn": {"forward": 0.1, "remat": 0.1, "backward": 0.2},
                   "lm_head": {"forward": 40 * ms, "remat": 20 * ms,
                               "backward": 70 * ms},
                   "loss": {"forward": 10 * ms, "remat": 5 * ms,
                            "backward": 15 * ms},
                   **({"ut_exit": {"forward": 2 * ms, "backward": 1 * ms}}
                      if scopes else {})},
        "kernels": {"flash_fwd": 0.07, "flash_dkv": 0.13},
        "unscoped_s": 0.0}
    return {"devices": [device]}


def _run(trace, conf, step_metrics=None):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": step_metrics or {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": ROWS, "seq": SEQ}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 9000.0}}


def test_the_readers_and_the_flop_module_import_no_jax():
    """The driver's process reads them and fails a run if JAX is
    imported."""
    import subprocess
    import sys

    code = ("import sys, importlib.util, os\n"
            "from benchmark import flops_ouro\n"
            "for m in %r:\n"
            "    spec = importlib.util.spec_from_file_location('_m', "
            "os.path.join(%r, 'layer_metrics', m + '.py'))\n"
            "    mod = importlib.util.module_from_spec(spec)\n"
            "    spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'jax'\n" % (METRICS, BENCH))
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(BENCH))


def test_the_five_readers_on_a_made_up_run():
    conf = _conf()
    run = _run(_trace(), conf, {"ut_steps": 4.0, "ut_expected_steps": 2.49,
                                "ut_exit_entropy": 1.21})
    read = lambda name: _reader(name).read(run)  # noqa: E731
    assert read("ut.exit_pct") == pytest.approx(100 * 3.0 / 900)
    assert read("ut.head_loss_ms") == pytest.approx(160.0)
    needed = flops_ouro.head_step_flops(conf, ROWS * SEQ) / 197e12
    assert needed == pytest.approx(0.1005, rel=1e-3)
    assert read("ut.head_loss_roofline") == pytest.approx(
        100 * needed / 0.160)
    assert 0.0 < read("ut.head_loss_roofline") <= 100.0
    assert read("ut.expected_steps") == 2.49
    assert 1.0 <= read("ut.expected_steps") <= PASSES
    assert read("ut.exit_entropy") == 1.21
    assert 0.0 <= read("ut.exit_entropy") <= math.log(PASSES)
    # the list-free readers hold this cell without an edit: the exits lie
    # in the heads' share, T x N flash calls in the roofline's count
    assert _reader("step.head_loss_pct").read(run) == pytest.approx(
        100 * 160.0 / 900)
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * flops_ouro.flash_step_flops(conf, ROWS, SEQ) / 197e12 / 0.2)
    assert _reader("train_step.mfu_pct").read(run) == pytest.approx(
        100 * 9000.0 * flops_ouro.train_flops_per_token(conf, SEQ) / 197e12)


def test_the_roofline_cannot_pass_100_unless_the_count_is_wrong():
    """Exits that took exactly their needed products' time at the bf16
    peak read 100; a program that makes every pass's logits again (a fourth
    product an exit) at the peak reads 75, never over 100."""
    conf = _conf()
    needed = flops_ouro.head_step_flops(conf, ROWS * SEQ) / 197e12
    at_peak = _trace()
    at_peak["devices"][0]["scopes"]["lm_head"] = {
        "forward": needed / 3, "backward": 2 * needed / 3}
    at_peak["devices"][0]["scopes"]["loss"] = {}
    assert _reader("ut.head_loss_roofline").read(
        _run(at_peak, conf)) == pytest.approx(100.0)
    at_peak["devices"][0]["scopes"]["lm_head"]["remat"] = needed / 3
    assert _reader("ut.head_loss_roofline").read(
        _run(at_peak, conf)) == pytest.approx(75.0)


def test_on_a_program_without_the_passes_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all (an
    unknown ``LlamaConfig`` field: it fails at once); a program without the
    scope, an untraced run, a configuration of ONE head: None each time,
    and nothing raises."""
    conf = _conf()
    bare = _run(_trace(scopes=False), conf)
    assert _reader("ut.exit_pct").read(bare) is None
    for metric in ("ut.expected_steps", "ut.exit_entropy"):
        assert _reader(metric).read(bare) is None, metric
    for metric in METRICS:
        assert _reader(metric).read(_run(None, conf)) is None, metric
    mistral = _load("configs", "mistral-7b-v0.1-d4.json")
    for metric in ("ut.head_loss_ms", "ut.head_loss_roofline"):
        assert _reader(metric).read(_run(_trace(), mistral)) is None, metric
