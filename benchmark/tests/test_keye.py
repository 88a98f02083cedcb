"""The yardstick of the Keye-VL-2.0-30B-A3B cell: ``JAX_PLATFORMS=cpu python
-m pytest benchmark/tests/test_keye.py -q``.  Its cases need no chip, no
train loop and no compile: ``tests/test_yardstick.py`` collects them in
tier-1 by name.  Entries and cells are found BY NAME and lists held by
MEMBERSHIP, so that a later cell of the same mixer appends itself to this
cell's entries without an edit here."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_keye, trace_reduce
from benchmark.loops import train
from benchmark.reference import keye_sparse
from benchmark.tests.test_trinity import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "keye-vl-2.0-30b-a3b-1of8"
CELL = "keyevl2-train-s16384"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TRACED = ["dsa.time_share_pct", "dsa.index_ms", "dsa.select_ms",
          "dsa.attend_ms", "dsa.loss_ms", "dsa.attend_roofline",
          "dsa.index_roofline"]
METRICS = TRACED + ["dsa.selected_share"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share"]
CUT = {"num_hidden_layers": (48, 8), "num_local_experts": (128, 16),
       "vocab_size": (151936, 18992)}
INDEXER = {"indexer_head_dim": 64, "indexer_num_heads": 16,
           "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
           "q_chunk_size": 512, "topk": 2048}
SEQ = 16384
CAUSAL, SELECTED = 134225920, 31458304


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
    # the public file counts the experts under TWO keys: ONE is cut to the
    # experts held, the other stays the router's width
    assert (conf["num_experts"], conf["num_local_experts"]) == (128, 16)
    assert conf["sa_config"] == published["sa_config"] == INDEXER
    assert conf["share"] == {
        "chips_per_layer": 8, "vocabulary_over": 8, "leading_dense": None,
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count or routing number changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts", "num_experts_per_tok", "norm_topk_prob",
                "rms_norm_eps", "rope_theta", "rope_scaling",
                "max_position_embeddings", "tie_word_embeddings",
                "decoder_sparse_step", "mlp_only_layers", "sliding_window",
                "use_sliding_window", "max_window_layers"):
        assert conf[key] == published[key], key
    # what the public file does not settle is explained, a key each
    assert {"first_expert", "router_aux_loss_coef", "qk_head_norm",
            "idx_loss_coef", "rope", "indexer_input", "indexer_form",
            "chunk_sizes", "selection", "indexer_loss", "no_window",
            "intermediate_size", "initializer", "param_dtype", "dtype",
            "optimizer", "data"} <= set(conf["assumed"])
    assert "modeling_keye_vl2.py" in conf["assumed"]["indexer_input"]["why"]
    assert "LOWER" in conf["assumed"]["selection"]["value"]
    assert conf["scopes"] == ["dsa_index", "dsa_select", "dsa_loss"]
    assert conf["kernels"] == ["sparse_"]
    assert not any(s.startswith(tuple(conf["kernels"]))
                   for s in conf["scopes"])
    cfg = train.program_config(conf)
    assert cfg.layer_kinds == (("indexed", "moe"),) * 8
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps, cfg.rope_theta,
            cfg.tie_embeddings, cfg.qk_head_norm) == (
                2048, 32, 4, 128, 768, 18992, 1e-6, 10000000, False, True)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_experts, cfg.aux_loss_coef,
            cfg.leading_dense) == (
                128, 16, 0, 8, True, "softmax", False, 0, 0.0, 0)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.idx_loss_coef) == (16, 64, 2048, 1.0)
    kw = keye_sparse.layer_kwargs(conf)
    assert (kw["heads"], kw["kv_heads"], kw["index_heads"], kw["topk"],
            kw["theta"], kw["k"], kw["renormalise"], kw["first"]) == (
                32, 4, 16, 2048, 1e7, 8, True, 0)
    with pytest.raises(NotImplementedError):
        keye_sparse.layer_kwargs({**conf, "sa_config": {
            **INDEXER, "indexer_num_kv_heads": 2}})
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(num_local_experts=4), "4 experts held; a share keeps at least 8"),
    (dict(num_local_experts=32),
     "run 32 x chips_per_layer 8 is not the published"),
    (dict(vocab_size=9496), "under an eighth of the vocabulary"),
    (dict(num_experts=16), "num_experts: differs"),
    (dict(moe_intermediate_size=384), "moe_intermediate_size: differs"),
    (dict(hidden_size=1024), "hidden_size: differs"),
    (dict(sa_config={**INDEXER, "topk": 1024}), "sa_config: differs"),
    (dict(sa_config={**INDEXER, "indexer_head_dim": 32}),
     "sa_config: differs"),
    (dict(num_key_value_heads=2), "num_key_value_heads: differs"),
    (dict(num_experts_per_tok=4), "num_experts_per_tok: differs"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_each_floor_and_each_width_violated_in_turn(fault, said):
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    for key, value in fault.items():
        conf[key] = value
        if key in conf["reduced"]:
            conf["reduced"][key]["run"] = value
    faults = cuts.complaints(conf, published)
    assert any(said in f for f in faults), faults


def test_both_expert_keys_cut_is_a_complaint():
    """``num_experts`` and ``num_local_experts`` both match the rule's
    name for the experts' count: listing both under ``reduced`` is refused,
    so ONE is the experts held and the other the router's width."""
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    conf["num_experts"] = 16
    conf["reduced"]["num_experts"] = dict(
        conf["reduced"]["num_local_experts"])
    assert any("both of kind experts_held" in f
               for f in cuts.complaints(conf, published))


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-share-1x16384", 1)
    assert len(cell["why"]) <= 200 and "1024" in cell["why"] \
        and "8192" in cell["why"] and "23.4" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, SEQ, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the eight entries this cell brings stand behind what was there, in
    # order, on ONE layer; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + 8] == METRICS
    assert first > names.index("setup.lag_s")
    layer = per_layer[METRICS[0]]["layer"]
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        assert entry == {
            "name": name, "unit": entry["unit"],
            "better": "higher" if name.endswith("_roofline") else "lower",
            "source": ("device_trace" if name in TRACED
                       else "program_counter"),
            "layer": layer, "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert {per_layer[n]["unit"] for n in METRICS if n.endswith("_ms")} == {
        "ms"}
    assert {per_layer[n]["unit"] for n in METRICS
            if n.endswith(("_roofline", "_pct"))} == {"%"}
    for name in APPENDED_TO:    # appended: behind every cell that was there
        assert CELL in per_layer[name]["workloads"][1:]
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS + APPENDED_TO)
    # one chip: the four-chip cells are as many as they were
    assert sum(c["chips"] == 4 for c in bench["workloads"]) * 4 <= len(
        bench["workloads"])
    assert keye_sparse.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert keye_sparse.STEP_METRICS["dsa_selected_off"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "dsa_selected_share"} <= set(keye_sparse.STEP_METRICS)


def test_flash_dq_ms_lists_the_cells_that_were_there_and_not_this_one():
    """The metric reads null in every cell since the backward pass became
    one kernel; it carries the list of the fifteen cells accepted then, so
    that a cell added later is not held to a kernel that no longer
    exists.  Nothing else of the entry moved."""
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == "flash.dq_ms"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "flash.dq_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s"}
    cells = [c["name"] for c in bench["workloads"]]
    assert entry["workloads"] == cells[:15] and CELL not in entry["workloads"]
    assert cells.index(CELL) >= 15


def as_accepted(case):
    """``case`` of an older cell's file run on ``BENCHMARK.json`` as that
    file's PR knew it in ONE respect: ``flash.dq_ms`` without its list.  Six
    older cases hold "the entries that list my cell" as a CLOSED set, which
    the list this PR had to give that entry (it names their cells) opens;
    their files are the benchmark's and are not this PR's to edit, so
    ``tests/test_yardstick.py`` collects them through here until a
    ``benchmark`` PR retires the entry (ROADMAP S11 (i))."""
    import functools
    import sys

    module = sys.modules[case.__module__]

    @functools.wraps(case)
    def run():
        load = module._load

        def accepted(*path):
            data = load(*path)
            if path[-1] == "BENCHMARK.json":
                for m in data["per_layer"]:
                    if m["name"] == "flash.dq_ms":
                        m.pop("workloads", None)
            return data

        module._load = accepted
        try:
            case()
        finally:
            module._load = load

    return run


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 852988928),
                                         (True, 30640656384)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (853.0 M:
    ISSUE 70's count) and of the published model (30.64 B: the name's
    30B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_keye.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_selected_pairs_and_the_indexer():
    """Hand counts at the published widths: 39.96 TFLOP a step needed (2.44
    GFLOP a token); attention over the SELECTED pairs 12.37 of them (the
    causal pairs would be 52.8), the index scores 3.23 and the indexer's
    projections 1.78, the held experts' rows 3.71."""
    conf = _conf()
    count = flops_keye
    assert flops.of(conf) is count and flops.counts_experts(conf)
    attention = 2048 * 128 * (2 * 32 + 2 * 4)
    indexer = 2048 * (16 * 64 + 64 + 16)
    expert = 3 * 2048 * 768
    assert (attention, indexer, expert) == (18874368, 2260992, 4718592)
    assert (count.attention_params(conf), count.indexer_params(conf)) == (
        attention, indexer)
    assert count.held_per_token(conf) == 1.0               # 8 x 16 / 128
    matmul = 8 * (attention + indexer + 2048 * 128 + expert) + 2048 * 18992
    assert count.active_matmul_params(conf) == matmul
    # the selection: a query reads min(t + 1, 2048) keys
    assert SEQ * (SEQ + 1) // 2 == CAUSAL
    assert count.selected_pairs(conf, SEQ) == SELECTED == (
        2048 * 2049 // 2 + (SEQ - 2048) * 2048)
    assert SELECTED / CAUSAL == pytest.approx(0.2344, abs=5e-5)
    assert count.selected_pairs(conf, 2048) == 2048 * 2049 // 2  # all of them
    flash = 12 * 32 * 128 * 8 * SELECTED
    assert count.flash_step_flops(conf, 1, SEQ) == flash \
        == pytest.approx(12.37e12, rel=1e-3)
    assert flash * CAUSAL / SELECTED == pytest.approx(52.78e12, rel=1e-3)
    pairs = 8 * 2 * 16 * 64 * (CAUSAL + 2 * SELECTED)
    assert count.index_pair_flops(conf, 1, SEQ) == pairs \
        == pytest.approx(3.230e12, rel=1e-3)
    assert count.index_step_flops(conf, 1, SEQ) == \
        pairs + 6 * 8 * indexer * SEQ == pytest.approx(5.008e12, rel=1e-3)
    per_token = count.train_flops_per_token(conf, SEQ)
    assert per_token == 6 * matmul + (flash + pairs) / SEQ
    assert per_token == pytest.approx(2.439e9, rel=1e-3)
    assert per_token * SEQ == pytest.approx(39.96e12, rel=1e-3)
    # the indexer and the attention over its selection: 44 % of what is needed
    assert (flash + count.index_step_flops(conf, 1, SEQ)) / (
        per_token * SEQ) == pytest.approx(0.435, abs=0.005)
    # compute-bound both: 62.8 ms of operations against 8.8 of bytes, 25.4
    # against 2.9
    q, kv = SEQ * 32 * 128 * 2, SEQ * 4 * 128 * 2
    assert count.flash_step_bytes(conf, 1, SEQ) == 8 * (6 * q + 6 * kv)
    assert flops.roofline_seconds(flash, count.flash_step_bytes(
        conf, 1, SEQ), PEAK) == {"seconds": flash / 197e12,
                                 "bound": "compute"}
    assert flops.roofline_seconds(
        count.index_step_flops(conf, 1, SEQ),
        count.index_step_bytes(conf, 1, SEQ), PEAK)["bound"] == "compute"
    # the grouped products over the rows HELD: one of a token's eight, 1024
    # rows an expert where the deployment's sees 8192
    assert count.experts_step_flops(conf, 1, SEQ) == \
        6 * SEQ * 8 * expert == pytest.approx(3.711e12, rel=1e-3)
    assert SEQ * 8 / 128 == 1024 and 8 * SEQ * 8 / 128 == 8192
    rows, weights = 9 * SEQ * (2048 + 768) * 2, 3 * 16 * expert * 2
    assert count.experts_step_bytes(conf, 1, SEQ) == 8 * (rows + weights)
    # at the published depth and experts: 48 layers, 8 experts a token
    whole = _whole(conf)
    assert count.held_per_token(whole) == 8.0


def _run(trace, conf, seq=SEQ, step_metrics=None):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": step_metrics or {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": seq}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 10000.0}}


def _dsa_planes():
    """``test_trinity.py``'s synthetic planes with the windowed kernels'
    three passes named ``flash_*_dsa`` (60 + 80 + 100 ns a step under
    ``attention``, beside the plain kernels' 80) and three XLA ops moved
    under the new scopes: ``dsa_index`` 100 ns, ``dsa_select`` 20,
    ``dsa_loss`` 40."""
    planes, names = _planes("_dsa")
    moved = {"attn_qkv/dot_general": "dsa_index/dot_general",
             "moe_route/dot_general": "dsa_select/top_k",
             "moe_dispatch/gather": "dsa_loss/exp2"}

    def move(stack):
        for old, new in moved.items():
            stack = stack.replace(old, new)
        return stack

    return planes, {plane: {event: move(stack)
                            for event, stack in events.items()}
                    for plane, events in names.items()}


def _trace(conf, planes_and_names):
    planes, names = planes_and_names
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_the_eight_readers_on_synthetic_planes():
    conf = _conf()
    run = _run(_trace(conf, _dsa_planes()), conf,
               step_metrics={"dsa_selected_share": 0.2344})
    assert _reader("dsa.index_ms").read(run) == pytest.approx(100e-6)
    assert _reader("dsa.select_ms").read(run) == pytest.approx(20e-6)
    assert _reader("dsa.loss_ms").read(run) == pytest.approx(40e-6)
    # the kernels under the data mask alone, not the plain ones beside them
    assert _reader("dsa.attend_ms").read(run) == pytest.approx(240e-6)
    # the three scopes and ``attention`` (the plain kernels' 80 ns too)
    assert _reader("dsa.time_share_pct").read(run) == pytest.approx(
        100 * (100 + 20 + 40 + 240 + 80) / 1000)
    attend = _reader("dsa.attend_roofline")
    assert attend.bound(run) == "compute"
    assert attend.read(run) == pytest.approx(
        100 * (12 * 32 * 128 * 8 * SELECTED / 197e12) / 240e-9, rel=1e-6)
    index = _reader("dsa.index_roofline")
    assert index.bound(run) == "compute"
    assert index.read(run) == pytest.approx(
        100 * (flops_keye.index_step_flops(conf, 1, SEQ) / 197e12) / 100e-9,
        rel=1e-6)
    assert _reader("dsa.selected_share").read(run) == 0.2344
    # the older readers of the flash kernels hold these calls too
    assert _reader("flash.fwd_ms").read(run) == pytest.approx(80e-6)
    assert _reader("flash.dkv_ms").read(run) == pytest.approx(130e-6)


def test_on_a_program_without_the_indexer_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all; a
    program without a ``dsa_*`` scope or a ``flash_*_dsa`` kernel, an
    untraced run, a run whose reference keeps no ``dsa_selected_share``, a
    configuration whose FLOP module counts no selection: None each time,
    and nothing raises."""
    conf = _conf()
    plain = _run(_trace(conf, _planes("_win")), conf)
    for metric in METRICS:
        assert _reader(metric).read(plain) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    no_window = _run(None, conf)
    del no_window["worker"]["window"]["step_metrics"]
    assert _reader("dsa.selected_share").read(no_window) is None
    mellum = _load("configs", "mellum2-12b-a2.5b-1of4.json")
    there = _run(_trace(mellum, _dsa_planes()), mellum)
    for metric in ("dsa.attend_roofline", "dsa.index_roofline"):
        assert _reader(metric).read(there) is None, metric
