"""The yardstick of the Kimi-Linear-48B-A3B cell: ``JAX_PLATFORMS=cpu python
-m pytest benchmark/tests/test_kimi_linear.py -q``.  Its cases need no chip,
no train loop and no compile: ``tests/test_yardstick.py`` collects them in
tier-1 by name."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_kimi_linear, trace_reduce
from benchmark.loops import train
from benchmark.reference import kimi_linear
from benchmark.tests.test_trinity import _planes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b-1of16"
CELL = "kimilinear-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["kda.time_share_pct", "kda.scan_ms", "kda.conv_ms",
           "kda.kernel_ms", "kda.scan_roofline"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share"]
CUT = {"num_hidden_layers": (27, 8), "num_experts": (256, 16),
       "vocab_size": (163840, 20480)}
KDA = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25,
       26]
FULL = [4, 8, 12, 16, 20, 24, 27]


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


def test_the_file_is_the_catalog_row_cut_to_one_chip_of_sixteen():
    conf, published = _conf(), _load("testdata", "published", NAME + ".json")
    assert cuts.complaints(conf, published) == []
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "experts_held", "vocabulary"]
    # the nested lists stay verbatim: they count layers from 1, and the
    # first 8 are the dense layer + the whole period K K F K + three more
    linear = conf["linear_attn_config"]
    assert linear == published["linear_attn_config"] == {
        "full_attn_layers": FULL, "head_dim": 128, "kda_layers": KDA,
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert sorted(KDA + FULL) == list(range(1, 28))
    kinds = kimi_linear.kinds(conf)
    assert [m for m, _ in kinds] == ["kda"] * 3 + ["latent"] + [
        "kda"] * 3 + ["latent"]
    assert [f for _, f in kinds] == ["dense"] + ["moe"] * 7
    assert cuts.leading_dense(published, "first_k_dense_replace") == 1
    assert conf["share"] == {
        "chips_per_layer": 16, "vocabulary_over": 8,
        "leading_dense": "first_k_dense_replace",
        "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count, rank or routing number changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_token",
                "num_shared_experts", "routed_scaling_factor",
                "moe_renormalize", "moe_router_activation_func",
                "first_k_dense_replace", "rms_norm_eps", "mla_use_nope",
                "num_nextn_predict_layers", "tie_word_embeddings"):
        assert conf[key] == published[key], key
    assert conf["q_lora_rank"] is None and conf["mla_use_nope"] is True
    # what the public file does not settle is explained, a key each
    assert {"first_expert", "topk_method", "bias_update_speed",
            "router_aux_loss_coef", "position_embedding_type", "head_dim",
            "kda_projection_order", "kda_conv_bias", "kda_decay",
            "kda_output_gate", "kda_beta", "l2_norm_eps", "chunk",
            "topk_norm_eps", "mtp_head", "initializer", "param_dtype",
            "dtype", "optimizer", "data"} <= set(conf["assumed"])
    assert conf["scopes"] == ["kda_in", "kda_conv", "kda_scan", "kda_out"]
    assert conf["kernels"] == ["kdarule_"]
    # a kernel's prefix may not start a scope's name (the first match on
    # the name stack wins)
    assert not any(s.startswith(tuple(conf["kernels"]))
                   for s in conf["scopes"])
    cfg = train.program_config(conf)
    assert cfg.layer_kinds == kinds
    assert (cfg.embed_dim, cfg.num_heads, cfg.head_dim, cfg.mlp_dim,
            cfg.dense_width, cfg.vocab_size, cfg.norm_eps,
            cfg.tie_embeddings) == (2304, 32, 128, 1024, 9216, 20480, 1e-5,
                                    False)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.router_scoring,
            cfg.select_bias, cfg.shared_experts, cfg.routed_scaling_factor,
            cfg.aux_loss_coef, cfg.leading_dense) == (
                256, 16, 0, 8, True, "sigmoid", True, 1, 2.446, 0.0, 1)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.latent_qk_dim, cfg.v_head_dim,
            cfg.position_embedding, cfg.rotary(False)) == (
                32, 128, 4, None, 512, 192, 128, "nope", False)
    kw = kimi_linear.layer_kwargs(conf)
    assert (kw["kda_heads"], kw["kda_dim"], kw["heads"], kw["nope"],
            kw["rope"], kw["v_dim"], kw["latent"], kw["k"], kw["factor"],
            kw["first"]) == (32, 128, 32, 128, 64, 128, 512, 8, 2.446, 0)
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("fault,said", [
    (dict(num_hidden_layers=4), "3 layers after the 1 leading dense"),
    (dict(num_experts=4), "4 experts held; a share keeps at least 8"),
    (dict(num_experts=32), "run 32 x chips_per_layer 16 is not the published"),
    (dict(vocab_size=10240), "under an eighth of the vocabulary"),
    (dict(moe_intermediate_size=512), "moe_intermediate_size: differs"),
    (dict(kv_lora_rank=256), "kv_lora_rank: differs"),
    (dict(linear_attn_config={"num_heads": 8}), "linear_attn_config: differs"),
    (dict(num_experts_per_token=4), "num_experts_per_token: differs"),
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
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert len(bench["workloads"]) >= 13   # found by name: later cells pass
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-1x8192", 1)
    assert len(cell["why"]) <= 200
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, 8192, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    # the five entries this cell brings stand behind what was there, in order
    first = names.index(METRICS[0])
    assert names[first:first + 5] == METRICS
    assert first > names.index("flash.full_ms")
    gdn = per_layer["gdn.scan_ms"]
    for name in METRICS:
        assert per_layer[name] == {
            "name": name, "unit": per_layer[name]["unit"],
            "better": "higher" if "roofline" in name else "lower",
            "source": "device_trace", "layer": gdn["layer"],
            "moves": "train_tokens_per_s", "workloads": [CELL]}
        # the scalar rule's entry of the same kind, under the other name
        assert per_layer[name]["unit"] == per_layer[
            name.replace("kda.", "gdn.")]["unit"]
    for name in APPENDED_TO:    # appended: behind every cell that was there
        assert CELL in per_layer[name]["workloads"][1:]
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(
                      METRICS + APPENDED_TO)
    # thirteen cells, still two on four chips: a quarter of 13 is 3
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(CELL) + 1]
    assert len(upto) == 13 and sum(c["chips"] == 4 for c in upto) == 2
    assert kimi_linear.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean", "kda_state_absmax",
            "kda_chunk_decay_min"} <= set(kimi_linear.STEP_METRICS)


def test_rope_kernel_ms_stands_as_it_was_before_this_cells_five_entries():
    """What ``test_rope_kernel_ms.py``'s first case holds of PR 58's entry
    — ``flash.fwd_ms``'s fields under its own name, its three cells — but
    for "the last entry": this PR's five stand behind it (that file is the
    benchmark's and is not edited; tier-1 collects this case)."""
    bench = _load(os.pardir, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    fwd, = [m for m in bench["per_layer"] if m["name"] == "flash.fwd_ms"]
    at = names.index("rope.kernel_ms")
    assert bench["per_layer"][at] == {
        **fwd, "name": "rope.kernel_ms", "workloads": [
            "mistral7b-train-s4096", "mistral7b-train-s512",
            "olmoe-train-s4096"]}
    assert names[at + 1:at + 6] == METRICS


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1299826624),
                                         (True, 49122681728)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (1299.8 M:
    ISSUE 59's "about 1300 M") and of the published model (49.12 B: the
    name's 48B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_kimi_linear.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_recurrence_the_held_rows_and_the_two_latent_layers():
    """Hand counts at the published widths: 28.4 TFLOP a step needed; the
    six KDA layers' projections 11.6 of them, their recurrence 0.46, the
    two latent layers' attention 4.1, the held experts' rows 1.2."""
    conf = _conf()
    count = flops_kimi_linear
    assert flops.of(conf) is count and flops.counts_experts(conf)
    assert (count.kda_layers(conf), count.latent_layers(conf),
            count.expert_layers(conf)) == (6, 2, 7)
    kda = 2304 * (3 * 4096 + 2 * 128 + 32) + 2 * 128 * 4096 + 4096 * 2304
    latent = (2304 * 32 * 192 + 2304 * (512 + 64) + 512 * 32 * 256
              + 32 * 128 * 2304)
    expert = 3 * 2304 * 1024
    assert (kda, latent, expert) == (39460864, 29114368, 7077888)
    assert (count.kda_params(conf), count.latent_params(conf),
            count.expert_params(conf)) == (kda, latent, expert)
    assert count.held_per_token(conf) == 0.5               # 8 x 16 / 256
    matmul = (6 * kda + 2 * latent + 3 * 2304 * 9216
              + 7 * (2304 * 256 + 1.5 * expert) + 2304 * 20480)
    assert count.active_matmul_params(conf) == matmul
    assert 6 * 6 * kda * 8192 == pytest.approx(11.64e12, rel=1e-3)
    # the recurrence: 18 x 128 x 128 a token and head, six layers of 32
    rule = 18 * 6 * 32 * 128 * 128
    assert count.kda_flops_per_token(conf) == rule
    assert count.kda_step_flops(conf, 1, 8192) == rule * 8192 \
        == pytest.approx(0.464e12, rel=1e-3)
    flash = 3 * 2 * 8192 * 32 * (192 + 128) * 8192
    assert count.flash_step_flops(conf, 1, 8192) == flash \
        == pytest.approx(4.12e12, rel=1e-3)
    per_token = count.train_flops_per_token(conf, 8192)
    assert per_token == 6 * matmul + flash / 8192 + rule
    assert per_token * 8192 == pytest.approx(28.39e12, rel=1e-3)
    # bytes of one layer's rule: forward 403.7 MB, backward 740.3 MB
    wide = 8192 * 4096
    forward, backward = count.kda_pass_bytes(conf, 1, 8192)
    assert forward == 4 * wide * 2 + wide * 4 + 8192 * 32 * 4
    assert backward == 7 * wide * 2 + 2 * wide * 4 + 2 * 8192 * 32 * 4
    assert count.kda_step_bytes(conf, 1, 8192) == 6 * (forward + backward)
    assert (forward / 1e6, backward / 1e6) == (
        pytest.approx(403.7, abs=0.1), pytest.approx(740.3, abs=0.1))
    assert count.kda_scan_ceiling_pct(conf, 1, 8192) == pytest.approx(
        73.9, abs=0.05)
    # memory-bound: 8.38 ms of HBM traffic against 2.35 ms of operations
    assert flops.roofline_seconds(
        count.kda_step_flops(conf, 1, 8192),
        count.kda_step_bytes(conf, 1, 8192), PEAK) == {
            "seconds": 6 * (forward + backward) / 819e9, "bound": "memory"}
    # the grouped products over the rows HELD: 4096 of 65536 a layer
    assert count.experts_step_flops(conf, 1, 8192) == \
        6 * 8192 * 7 * 0.5 * expert == pytest.approx(1.218e12, rel=1e-3)
    rows, weights = 9 * 4096 * (2304 + 1024) * 2, 3 * 16 * expert * 2
    assert count.experts_step_bytes(conf, 1, 8192) == 7 * (rows + weights)
    # the latent layers' k as the MODEL has it: 32 x 128 + the ONE 64
    q, k, v = 8192 * 32 * 192 * 2, 8192 * (32 * 128 + 64) * 2, \
        8192 * 32 * 128 * 2
    assert count.flash_step_bytes(conf, 1, 8192) == 2 * 3 * (q + k + 2 * v)
    # at the published depth and experts: 20 KDA : 7 latent, 8 a token
    whole = _whole(conf)
    assert (count.kda_layers(whole), count.latent_layers(whole),
            count.held_per_token(whole)) == (20, 7, 8.0)


def _run(trace, conf, seq=8192):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}},
                       "check": {"program_parts": {}}},
            "conf": conf, "job": {"rows": 1, "seq": seq}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 20000.0}}


def _kda_planes():
    """``test_trinity.py``'s synthetic planes with the flash kernels' name
    stacks moved under the KDA scopes: the windowed kernels (60 + 80 + 100
    ns a step) stand for ``kdarule_fwd``, its rematerialised run and
    ``kdarule_bwd`` under ``kda_scan``, the plain ones (20 + 30 + 30) for
    XLA ops under ``kda_conv``."""
    planes, names = _planes("_win")

    def moved(stack):
        if "_win" in stack:
            kernel = "kdarule_bwd" if "transpose(" in stack else "kdarule_fwd"
            return stack.replace("attention", "kda_scan").replace(
                "flash_fwd_win", kernel).replace(
                    "flash_dq_win", kernel).replace("flash_dkv_win", kernel)
        return stack.replace("attention", "kda_conv")

    return planes, {plane: {event: moved(stack)
                            for event, stack in events.items()}
                    for plane, events in names.items()}


def _trace(conf, planes_and_names):
    planes, names = planes_and_names
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_the_five_readers_on_synthetic_planes():
    conf = _conf()
    run = _run(_trace(conf, _kda_planes()), conf)
    scan_ms = _reader("kda.scan_ms").read(run)
    assert scan_ms == pytest.approx(240e-6)
    assert _reader("kda.conv_ms").read(run) == pytest.approx(80e-6)
    assert _reader("kda.kernel_ms").read(run) == pytest.approx(240e-6)
    share = _reader("kda.time_share_pct").read(run)
    assert 0.0 < share <= 100.0
    least = 6 * (403.7e6 + 740.3e6) / 819e9
    roofline = _reader("kda.scan_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * least / 240e-9, rel=1e-3)


def test_on_a_program_without_the_rule_the_readers_return_nothing():
    """The parent's program opens no ``kda_*`` scope and runs no
    ``kdarule_*`` kernel; an untraced run has no trace; a configuration
    whose FLOP module counts no such rule has no roofline: None each time,
    and nothing raises."""
    conf = _conf()
    plain = _run(_trace(conf, _planes("_win")), conf)
    for metric in METRICS:
        assert _reader(metric).read(plain) is None, metric
        assert _reader(metric).read(_run(None, conf)) is None, metric
    olmo = _load("configs", "olmo-hybrid-7b-d4.json")
    assert _reader("kda.scan_roofline").read(
        _run(_trace(olmo, _kda_planes()), olmo, seq=4096)) is None
    # the XLA form: the scope has time, no kernel is named
    xla = _trace(conf, _kda_planes())
    for d in xla["devices"]:
        d["kernels"] = {}
    assert _reader("kda.kernel_ms").read(_run(xla, conf)) is None
    assert _reader("kda.scan_ms").read(_run(xla, conf)) == pytest.approx(
        240e-6)
