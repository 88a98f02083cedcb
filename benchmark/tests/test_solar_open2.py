"""The yardstick of the Solar-Open2-250B cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_solar_open2.py -q``.  Its cases need no chip,
no train loop and no compile: ``tests/test_yardstick.py`` collects them in
tier-1 by name.  Entries and cells are found BY NAME and lists held by
MEMBERSHIP, so that a later cell of the same mixer appends itself to this
cell's entries without an edit here."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_solar_open2, trace_reduce
from benchmark.loops import train
from benchmark.reference import solar_open2
from benchmark.tests.test_kimi_linear import _kda_planes
from benchmark.tests.test_trinity import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "solar-open2-250b-1of32"
CELL = "solaropen2-train-s4096"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TRACED = ["kda_neg.time_share_pct", "kda_neg.proj_ms", "kda_neg.kernel_ms",
          "kda_neg.scan_roofline"]
METRICS = TRACED + ["kda_neg.beta_max"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share"]
CUT = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 10),
       "vocab_size": (196608, 24576)}
GQA = list(range(0, 48, 4))


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


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_thirty_two():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "experts_held", "vocabulary"]
    # the list of softmax layers stays verbatim: it counts from 0, and the
    # first 4 layers are one whole period G K K K
    assert conf["gqa_layers"] == published["gqa_layers"] == GQA
    assert conf["linear_attn_config"] == published["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    kinds = solar_open2.kinds(conf)
    assert kinds == (("attention", "moe"),) + (("kda", "moe"),) * 3
    assert cuts.leading_dense(published, "first_k_dense_replace") == 0
    assert conf["share"] == {
        "chips_per_layer": 32, "vocabulary_over": 8,
        "leading_dense": "first_k_dense_replace",
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count or routing number changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "norm_topk_prob",
                "first_k_dense_replace", "rms_norm_eps", "use_rope",
                "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
                "gqa_interval", "partial_rotary_factor", "rope_theta",
                "max_position_embeddings", "tie_word_embeddings"):
        assert conf[key] == published[key], key
    assert conf["kda_allow_neg_eigval"] is True and conf["use_rope"] is False
    # what the public file does not settle is explained, a key each
    assert {"first_expert", "scoring_func", "topk_method",
            "bias_update_speed", "router_aux_loss_coef",
            "position_embedding_type", "kda_low_rank", "kda_beta",
            "kda_value_heads", "kda_projection_order", "kda_conv_bias",
            "kda_decay", "kda_output_gate", "gqa_output_gate", "l2_norm_eps",
            "chunk", "shared_expert_width", "topk_norm_eps", "initializer",
            "param_dtype", "dtype", "optimizer", "data"} <= set(
                conf["assumed"])
    assert conf["scopes"] == ["kda_in", "kda_conv", "kda_scan", "kda_out"]
    assert conf["kernels"] == ["kdarule_"]
    assert not any(s.startswith(tuple(conf["kernels"]))
                   for s in conf["scopes"])
    cfg = train.program_config(conf)
    assert cfg.layer_kinds == kinds
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps,
            cfg.tie_embeddings) == (4096, 64, 8, 128, 1280, 24576, 1e-5,
                                    False)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_experts, cfg.routed_scaling_factor,
            cfg.aux_loss_coef, cfg.leading_dense) == (
                320, 10, 0, 8, True, "sigmoid", True, 1, 1, 0.0, 0)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
            cfg.kda_neg_eigval, cfg.attn_output_gate, cfg.position_embedding,
            cfg.rotary(False), cfg.kv_lora_rank) == (
                64, 128, 4, True, True, "nope", False, 0)
    kw = solar_open2.layer_kwargs(conf)
    assert (kw["kda_heads"], kw["kda_dim"], kw["beta_scale"], kw["heads"],
            kw["kv_heads"], kw["k"], kw["factor"], kw["first"]) == (
                64, 128, 2.0, 64, 8, 8, 1.0, 0)
    for flag in ("use_rope", "kda_use_full_proj"):
        with pytest.raises(NotImplementedError):
            solar_open2.layer_kwargs({**conf, flag: True})
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=3), "3 layers after the 0 leading dense"),
    (dict(n_routed_experts=5), "5 experts held; a share keeps at least 8"),
    (dict(n_routed_experts=20),
     "run 20 x chips_per_layer 32 is not the published"),
    (dict(vocab_size=12288), "under an eighth of the vocabulary"),
    (dict(moe_intermediate_size=640), "moe_intermediate_size: differs"),
    (dict(hidden_size=2048), "hidden_size: differs"),
    (dict(linear_attn_config={"num_heads": 32}),
     "linear_attn_config: differs"),
    (dict(gqa_layers=[0]), "gqa_layers: differs"),
    (dict(kda_allow_neg_eigval=False), "kda_allow_neg_eigval: differs"),
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


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-share-1x4096", 1)
    assert len(cell["why"]) <= 200 and "102" in cell["why"]
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, 4096, None, 1, 2, 4)
    assert "102" in job["why"] and "3277" in job["why"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = list(per_layer)
    # the five entries this cell brings stand behind what was there, in
    # order, on the mixer's layer; LATER cells may join their lists
    first = names.index(METRICS[0])
    assert names[first:first + 5] == METRICS
    assert first > names.index("kda.scan_roofline")
    kda = per_layer["kda.scan_roofline"]
    for name in METRICS:
        entry = dict(per_layer[name])
        assert CELL in entry.pop("workloads")
        assert entry == {
            "name": name, "unit": entry["unit"],
            "better": "lower" if name in TRACED[:3] else "higher",
            "source": ("device_trace" if name in TRACED
                       else "program_counter"),
            "layer": kda["layer"], "moves": "train_tokens_per_s"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    # Kimi-Linear's entry of the same quantity, under the other name
    for name in ("time_share_pct", "kernel_ms", "scan_roofline"):
        assert per_layer["kda_neg." + name]["unit"] == per_layer[
            "kda." + name]["unit"]
    assert per_layer["kda_neg.proj_ms"]["unit"] == "ms"
    for name in APPENDED_TO:    # appended: behind every cell that was there
        assert CELL in per_layer[name]["workloads"][1:]
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS + APPENDED_TO)
    # one chip: the four-chip cells are as many as they were
    assert sum(c["chips"] == 4 for c in bench["workloads"]) * 4 <= len(
        bench["workloads"])
    assert solar_open2.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean", "kda_beta_max",
            "kda_state_absmax", "kda_chunk_decay_min"} <= set(
                solar_open2.STEP_METRICS)


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1420916544),
                                         (True, 250287810304)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (1420.9 M:
    ISSUE 64's count) and of the published model (250.29 B: the name's
    250B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_solar_open2.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_recurrence_the_held_rows_and_the_one_softmax_layer():
    """Hand counts at the published widths: 18.4 TFLOP a step needed (4.50
    GFLOP a token); the three KDA layers' projections 10.2 of them, their
    recurrence 0.23, the one softmax layer's attention 0.82 and its
    projections 2.7, the held experts' rows 0.39."""
    conf = _conf()
    count = flops_solar_open2
    assert flops.of(conf) is count and flops.counts_experts(conf)
    assert (count.kda_layers(conf), count.softmax_layers(conf)) == (3, 1)
    kda = 4096 * (3 * 8192 + 2 * 128 + 64) + 2 * 128 * 8192 + 8192 * 4096
    softmax = 4096 * 128 * (3 * 64 + 2 * 8)
    expert = 3 * 4096 * 1280
    assert (kda, softmax, expert) == (137625600, 109051904, 15728640)
    assert (count.kda_params(conf), count.softmax_params(conf),
            count.expert_params(conf)) == (kda, softmax, expert)
    assert count.held_per_token(conf) == 0.25              # 8 x 10 / 320
    matmul = (3 * kda + softmax + 4 * (4096 * 320 + 1.25 * expert)
              + 4096 * 24576)
    assert count.active_matmul_params(conf) == matmul
    assert 6 * 3 * kda * 4096 == pytest.approx(10.15e12, rel=1e-3)
    # the recurrence: 18 x 128 x 128 a token and head, three layers of 64
    rule = 18 * 3 * 64 * 128 * 128
    assert count.kda_flops_per_token(conf) == rule
    assert count.kda_step_flops(conf, 1, 4096) == rule * 4096 \
        == pytest.approx(0.232e12, rel=1e-3)
    flash = 6 * 4096 * 64 * 128 * 4096
    assert count.flash_step_flops(conf, 1, 4096) == flash \
        == pytest.approx(0.825e12, rel=1e-3)
    per_token = count.train_flops_per_token(conf, 4096)
    assert per_token == 6 * matmul + flash / 4096 + rule
    assert per_token == pytest.approx(4.497e9, rel=1e-3)
    assert per_token * 4096 == pytest.approx(18.42e12, rel=1e-3)
    # bytes of one layer's rule: 64 heads at 4096 tokens are Kimi-Linear's
    # 32 at 8192 — forward 403.7 MB, backward 740.3 MB, the same ceiling
    wide = 4096 * 8192
    forward, backward = count.kda_pass_bytes(conf, 1, 4096)
    assert forward == 4 * wide * 2 + wide * 4 + 4096 * 64 * 4
    assert backward == 7 * wide * 2 + 2 * wide * 4 + 2 * 4096 * 64 * 4
    assert count.kda_step_bytes(conf, 1, 4096) == 3 * (forward + backward)
    assert (forward / 1e6, backward / 1e6) == (
        pytest.approx(403.7, abs=0.1), pytest.approx(740.3, abs=0.1))
    assert count.kda_scan_ceiling_pct(conf, 1, 4096) == pytest.approx(
        73.9, abs=0.05)
    # memory-bound: 4.19 ms of HBM traffic against 1.18 ms of operations
    assert flops.roofline_seconds(
        count.kda_step_flops(conf, 1, 4096),
        count.kda_step_bytes(conf, 1, 4096), PEAK) == {
            "seconds": 3 * (forward + backward) / 819e9, "bound": "memory"}
    # the grouped products over the rows HELD: 1024 of 32768 a layer, 102
    # an expert where the deployment's sees 3277
    assert count.experts_step_flops(conf, 1, 4096) == \
        6 * 4096 * 4 * 0.25 * expert == pytest.approx(0.3865e12, rel=1e-3)
    assert 4096 * 8 / 320 == pytest.approx(102.4)
    assert 32 * 4096 * 8 / 320 == pytest.approx(3276.8)
    rows, weights = 9 * 1024 * (4096 + 1280) * 2, 3 * 10 * expert * 2
    assert count.experts_step_bytes(conf, 1, 4096) == 4 * (rows + weights)
    # the softmax layer's k and v are the 8 KV heads'
    q, kv = 4096 * 64 * 128 * 2, 4096 * 8 * 128 * 2
    assert count.flash_step_bytes(conf, 1, 4096) == 3 * (2 * q + 2 * kv)
    # at the published depth and experts: 36 KDA : 12 softmax, 8 a token
    whole = _whole(conf)
    assert (count.kda_layers(whole), count.softmax_layers(whole),
            count.held_per_token(whole)) == (36, 12, 8.0)


def _run(trace, conf, seq=4096, step_metrics=None):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": step_metrics or {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": seq}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 10000.0}}


def _proj_planes():
    """``test_kimi_linear.py``'s synthetic planes (``kdarule_*`` kernels,
    240 ns a step, under ``kda_scan``; 80 ns of XLA ops under ``kda_conv``)
    with the ops under ``kda_conv`` moved to ``kda_in``: a projection's."""
    planes, names = _kda_planes()
    return planes, {plane: {event: stack.replace("kda_conv", "kda_in")
                            for event, stack in events.items()}
                    for plane, events in names.items()}


def _trace(conf, planes_and_names):
    planes, names = planes_and_names
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_the_five_readers_on_a_made_up_run():
    conf = _conf()
    run = _run(_trace(conf, _proj_planes()), conf,
               step_metrics={"kda_beta_max": 1.93})
    assert _reader("kda_neg.kernel_ms").read(run) == pytest.approx(240e-6)
    assert _reader("kda_neg.proj_ms").read(run) == pytest.approx(80e-6)
    share = _reader("kda_neg.time_share_pct").read(run)
    assert 0.0 < share <= 100.0
    least = 3 * (403.7e6 + 740.3e6) / 819e9
    roofline = _reader("kda_neg.scan_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * least / 240e-9, rel=1e-3)
    assert _reader("kda_neg.beta_max").read(run) == 1.93
    # Kimi-Linear's readers of the same quantities read the same numbers
    for name in ("time_share_pct", "kernel_ms", "scan_roofline"):
        assert _reader("kda_neg." + name).read(run) == _reader(
            "kda." + name).read(run)


def test_on_a_program_without_the_rule_the_readers_return_nothing():
    """The parent's program cannot build this configuration at all; a
    program without a ``kda_*`` scope or a ``kdarule_*`` kernel, an untraced
    run, a run whose reference keeps no ``kda_beta_max``, a configuration
    whose FLOP module counts no such rule: None each time, and nothing
    raises."""
    conf = _conf()
    plain = _run(_trace(conf, _planes("_win")), conf)
    for metric in METRICS:
        assert _reader(metric).read(plain) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    no_window = _run(None, conf)
    del no_window["worker"]["window"]["step_metrics"]
    assert _reader("kda_neg.beta_max").read(no_window) is None
    olmo = _load("configs", "olmo-hybrid-7b-d4.json")
    assert _reader("kda_neg.scan_roofline").read(
        _run(_trace(olmo, _proj_planes()), olmo)) is None
    # the XLA form: the scopes have time, no kernel is named
    xla = _trace(conf, _proj_planes())
    for d in xla["devices"]:
        d["kernels"] = {}
    assert _reader("kda_neg.kernel_ms").read(_run(xla, conf)) is None
    assert _reader("kda_neg.proj_ms").read(_run(xla, conf)) == pytest.approx(
        80e-6)
