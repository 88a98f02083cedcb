"""The yardstick of the SDAR block-diffusion cell: ``JAX_PLATFORMS=cpu python
-m pytest benchmark/tests/test_sdar.py -q``.  Its cases need no chip, no
train loop and no compile: ``tests/test_yardstick.py`` collects them in
tier-1 by name.  Entries and cells are found BY NAME and lists held by
MEMBERSHIP, so that a later cell of the same objective appends itself to
this cell's entries without an edit here."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_sdar
from benchmark.loops import train
from benchmark.reference import sdar_block_diffusion

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "sdar-30b-a3b-chat-1of8"
CELL = "sdar-train-bd-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["bd.attend_ms", "bd.attend_roofline", "bd.executed_share",
           "bd.noise_ms", "bd.noise_pct", "bd.masked_share"]
SOURCES = {"bd.executed_share": "program_counter",
           "bd.masked_share": "program_counter"}
# a share cell's six expert lists (benchmark/README.md)
EXPERT_LISTS = ["moe.experts_roofline", "moe.load_max_over_mean",
                "moe.held_rows_share", "moe.rows_visited_share",
                "moe.token_rows_read_share", "moe.experts_xla_ms"]
CUT = {"num_hidden_layers": (48, 8), "num_experts": (128, 16),
       "vocab_size": (151936, 18992)}
SEQ, BLOCK = 8192, 4
NEEDED = SEQ * (SEQ + BLOCK)


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
    assert conf["share"] == {
        "chips_per_layer": 8, "vocabulary_over": 8, "leading_dense": None,
        "how": conf["share"]["how"]}
    assert "block-diffusion" in conf["deployment"] \
        and "WITHOUT the exchange" in conf["deployment"]
    # every assumed value says what settles it, the objective's above all
    group = conf["block_diffusion"]
    assert group == conf["assumed"]["block_diffusion"]["value"] == {
        "block_length": BLOCK, "mask_token_id": conf["vocab_size"] - 1,
        "eps": 0.001, "noise_seed": 0}
    why = conf["assumed"]["block_diffusion"]["why"]
    assert all(word in why for word in (
        "generate.py", "arXiv:2502.09992", "151669", "NO shift", "settle"))
    assert all(len(a["why"]) > 20 for a in conf["assumed"].values())
    # the program's fields: the router keeps its 128 outputs, 16 are held
    cfg = train.program_config(conf)
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.num_layers, cfg.num_experts,
            cfg.experts_held, cfg.num_selected, cfg.norm_topk_prob,
            cfg.qk_head_norm, cfg.rope_theta, cfg.aux_loss_coef) == (
                2048, 32, 4, 128, 768, 18992, 8, 128, 16, 8, True, True,
                1000000, 0.0)
    assert cfg.bd_block == BLOCK and cfg.bd_group == group
    assert cfg.layer_kinds == (("block_attention", "moe"),) * 8
    assert sdar_block_diffusion.layer_kwargs(conf) == dict(
        heads=32, kv_heads=4, block=BLOCK, theta=1e6, eps=1e-6, k=8,
        renormalise=True, first=0)
    assert conf["scopes"] == ["bd_noise"] and "kernels" not in conf
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(num_experts=4), "4 experts held"),
    (dict(vocab_size=9496), "under an eighth of the vocabulary"),
    (dict(hidden_size=1024), "hidden_size: differs"),
    (dict(moe_intermediate_size=384), "moe_intermediate_size: differs"),
    (dict(num_attention_heads=16), "num_attention_heads: differs"),
    (dict(num_experts_per_tok=4), "num_experts_per_tok: differs"),
    (dict(block_diffusion={"block_length": 8}),
     "block_diffusion: assumed states another value"),
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
        NAME, "train-bd-share-1x8192", 1)
    assert len(cell["why"]) <= 200 and "16384 rows" in cell["why"] \
        and "1024 rows" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, SEQ, None, 1, 2, 4)
    assert "DATA tokens" in job["why"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the six entries this cell brings stand behind what was there, in
    # order, on one layer; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS
    assert first > names.index("diffattn.roofline")
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        assert entry == {
            "name": name, "unit": entry["unit"],
            "better": "higher" if name.endswith("roofline") else "lower",
            "source": SOURCES.get(name, "device_trace"),
            "layer": "block diffusion", "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert {per_layer[n]["unit"] for n in METRICS if n.endswith("_ms")} == {
        "ms"}
    assert {per_layer[n]["unit"] for n in METRICS
            if n.endswith(("roofline", "_pct"))} == {"%"}
    # appended to a share cell's six expert lists and to NOTHING else: no
    # flash.dq_ms, no flash.window_*, no dsa.*, no rope.kernel_ms
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS) | set(
                EXPERT_LISTS)
    # one chip: the four-chip cells are as many as they were
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    assert len(bench["workloads"]) >= 18
    held = {k for k, (_, want) in sdar_block_diffusion.STEP_METRICS.items()
            if want is not None}
    assert held == {"moe_dropped", "bd_mask_off"}
    assert {"attn_bd_executed_share", "bd_masked_share"} <= set(
        sdar_block_diffusion.STEP_METRICS)


def test_the_parameter_count_is_init_params():
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated): 834.9 M, ISSUE 76's
    count."""
    from ray_tpu.models.llama import init_params

    conf = _conf()
    assert flops_sdar.total_params(conf) == 834899968
    shapes = jax.eval_shape(
        lambda k: init_params(k, train.program_config(conf)),
        jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 834899968


def test_flops_count_two_rows_a_token_one_through_the_head_and_the_needed_pairs():
    """Hand counts at the published widths, per DATA token: two rows
    through the projections, the router and the held experts' expected
    rows, one through the head, ``seq (seq + B)`` pairs a head and layer."""
    conf, count = _conf(), flops_sdar
    assert flops.of(conf) is count and flops.counts_experts(conf)
    d = 2048
    attention = 2 * d * 4096 + 2 * d * 512
    router, expert = d * 128, 3 * d * 768
    assert (attention, router, expert) == (18874368, 262144, 4718592)
    assert count.held_per_token(conf) == 1.0        # 8 x 16 / 128
    assert count.layer_matmul_params(conf) == attention + router + expert
    assert count.needed_pairs(conf, SEQ) == NEEDED == 67141632
    # half the causal square over the 2 seq rows, and a little
    assert NEEDED / (2 * SEQ * (2 * SEQ + 1) // 2) == pytest.approx(
        0.5, abs=3e-4)
    flash = 12 * 32 * 128 * 8 * NEEDED
    assert count.flash_step_flops(conf, 1, SEQ) == flash \
        == pytest.approx(2.64e13, rel=1e-3)
    per_token = count.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * (2 * 8 * (attention + router + expert)
                             + d * 18992) + flash / SEQ
    # the mechanism is most of the step's needed operations
    assert flash / (per_token * SEQ) == pytest.approx(0.561, abs=0.005)
    assert count.experts_step_flops(conf, 1, SEQ) == 6 * 2 * SEQ * 8 * expert
    rows = 2 * SEQ * (d + 768) * 2
    assert count.experts_step_bytes(conf, 1, SEQ) == 8 * (
        9 * rows + 3 * 16 * expert * 2)
    q, kv = 2 * SEQ * 32 * 128 * 2, 2 * SEQ * 4 * 128 * 2
    assert count.flash_step_bytes(conf, 1, SEQ) == 8 * (6 * q + 6 * kv)
    assert flops.roofline_seconds(flash, count.flash_step_bytes(
        conf, 1, SEQ), PEAK) == {"seconds": flash / 197e12,
                                 "bound": "compute"}


def _trace(kernels=True, scopes=True):
    """A hand-made reduced trace of four steps of 600 ms: the block rule's
    kernels 80 + 200 ms, 2 ms under ``bd_noise``."""
    ms = 1e-3
    flash = {"flash_fwd_bd": 80 * ms, "flash_dkv_bd": 200 * ms}
    device = {
        "steps": 4, "step_s": [0.6] * 4, "window_s": 2.4, "busy_s": 2.4,
        "idle_s": 0.0, "gap_s": [], "flash_s": 4 * 280 * ms,
        "scopes": {"attention": {"forward": 82 * ms, "backward": 204 * ms},
                   "moe_experts": {"forward": 0.05, "backward": 0.1},
                   **({"bd_noise": {"forward": 1.5 * ms,
                                    "backward": 0.5 * ms}} if scopes else {})},
        "kernels": flash if kernels else {"flash_fwd": 0.1},
        "unscoped_s": 0.0}
    return {"devices": [device]}


def _run(trace, conf, step_metrics=None):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": step_metrics or {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": SEQ}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 13000.0}}


def test_the_readers_and_the_flop_module_import_no_jax():
    """The driver's process reads them and fails a run if JAX is
    imported."""
    import subprocess
    import sys

    code = ("import sys, importlib.util, os\n"
            "from benchmark import flops_sdar\n"
            "for m in %r:\n"
            "    spec = importlib.util.spec_from_file_location('_m', "
            "os.path.join(%r, 'layer_metrics', m + '.py'))\n"
            "    mod = importlib.util.module_from_spec(spec)\n"
            "    spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'jax'\n" % (METRICS, BENCH))
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(BENCH))


def test_the_six_readers_on_a_made_up_run():
    conf = _conf()
    run = _run(_trace(), conf, {"attn_bd_executed_share": 1.062,
                                "bd_masked_share": 0.41})
    read = lambda name: _reader(name).read(run)  # noqa: E731
    assert read("bd.attend_ms") == pytest.approx(280.0)
    assert read("bd.noise_ms") == pytest.approx(2.0)
    assert read("bd.noise_pct") == pytest.approx(100 * 2.0 / 600)
    assert read("bd.executed_share") == 1.062
    assert read("bd.masked_share") == 0.41
    roofline = _reader("bd.attend_roofline")
    assert roofline.bound(run) == "compute"
    needed = flops_sdar.flash_step_flops(conf, 1, SEQ) / 197e12
    assert needed == pytest.approx(0.1340, rel=1e-3)
    assert read("bd.attend_roofline") == pytest.approx(100 * needed / 0.280)
    # the list-free readers of the flash kernels hold these calls too
    assert _reader("flash_roofline").read(run) == pytest.approx(
        100 * needed / 0.280)
    assert _reader("flash.fwd_ms").read(run) == pytest.approx(80.0)
    assert _reader("flash.dkv_ms").read(run) == pytest.approx(200.0)
    assert 0.0 < read("bd.attend_roofline") <= 100.0


def test_the_roofline_cannot_pass_100_unless_the_count_is_wrong():
    """Kernels that took exactly the needed operations' time at the bf16
    peak read 100; the count is the NEEDED pairs, so kernels as fast as the
    peak over the causal square of the 2 seq rows (twice the pairs, the
    mask-operand route) would read 50, never over 100."""
    conf = _conf()
    needed = flops_sdar.flash_step_flops(conf, 1, SEQ) / 197e12
    at_peak = _trace()
    at_peak["devices"][0]["kernels"] = {"flash_fwd_bd": needed / 3,
                                        "flash_dkv_bd": 2 * needed / 3}
    assert _reader("bd.attend_roofline").read(
        _run(at_peak, conf)) == pytest.approx(100.0)
    square = 12 * 32 * 128 * 8 * (2 * SEQ * (2 * SEQ + 1) // 2) / 197e12
    at_peak["devices"][0]["kernels"] = {"flash_fwd_bd": square}
    assert _reader("bd.attend_roofline").read(
        _run(at_peak, conf)) == pytest.approx(50.0, abs=0.03)


def test_on_a_program_without_the_objective_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all (an
    unknown ``LlamaConfig`` field: it fails at once); a program without the
    scope or the kernels, an untraced run, a configuration whose FLOP
    module counts no such pairs: None each time, and nothing raises."""
    conf = _conf()
    bare = _run(_trace(kernels=False, scopes=False), conf)
    for metric in METRICS:
        assert _reader(metric).read(bare) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    mellum = _load("configs", "mellum2-12b-a2.5b-1of4.json")
    assert _reader("bd.attend_roofline").read(_run(_trace(), mellum)) is None
