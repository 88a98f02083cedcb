"""The yardstick of the Trinity-Large-Preview cell: ``JAX_PLATFORMS=cpu
python -m pytest benchmark/tests/test_trinity.py -q``.  Its cases need no
chip, no train loop and no compile: ``tests/test_yardstick.py`` collects
them in tier-1 by name."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_afmoe, trace_reduce
from benchmark.loops import train
from benchmark.reference import afmoe

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "trinity-large-preview-1of32"
CELL = "trinity-train-s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ["flash.window_ms", "flash.window_roofline",
           "flash.window_executed_share"]
APPENDED_TO = ["moe.experts_roofline", "moe.load_max_over_mean",
               "moe.rows_visited_share", "moe.token_rows_read_share",
               "moe.experts_xla_ms", "moe.held_rows_share"]
S, F = "sliding_attention", "full_attention"
CUT = {"num_hidden_layers": (60, 5), "num_dense_layers": (6, 1),
       "layer_types": ([S, S, S, F] * 15, [S, S, S, F, S]),
       "num_experts": (256, 8), "vocab_size": (200192, 25024)}
WINDOW_PAIRS = 4096 * 4097 // 2 + 4096 * 4096      # 25.17 M at 8192
CAUSAL_PAIRS = 8192 * 8193 // 2                    # 33.56 M


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
    # the catalog row itself, as the rule's worked case has it
    assert published == _load("testdata", "published",
                              "trinity-large-preview.json")
    assert {k: (published[k], conf[k]) for k in published
            if conf[k] != published[k]} == CUT
    assert {k: (c["published"], c["run"]) for k, c in conf["reduced"].items()
            } == CUT
    assert [c["kind"] for c in conf["reduced"].values()] == [
        "depth", "leading_dense", "pattern", "experts_held", "vocabulary"]
    # one whole period behind the one dense layer: 3 windowed : 1 full
    after = conf["layer_types"][conf["num_dense_layers"]:]
    assert cuts.period(published["layer_types"][6:]) == 4 == len(after)
    assert (after.count(S), after.count(F)) == (3, 1)
    assert conf["share"] == {
        "chips_per_layer": 32, "vocabulary_over": 8,
        "leading_dense": "num_dense_layers", "how": conf["share"]["how"]}
    assert "WITHOUT the exchange" in conf["deployment"]
    # no width, head count, window, scale or experts-per-token changes
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "sliding_window", "route_scale", "num_experts_per_tok",
                "num_shared_experts", "rope_theta", "rms_norm_eps"):
        assert conf[key] == published[key], key
    # what the public file does not state is explained, a key each
    assert {"topk_method", "topk_norm_eps", "qk_head_norm", "first_expert",
            "attn_output_gate", "block_norm", "position_embedding_type",
            "embedding_multiplier", "embedding_init_std", "rope_pairing",
            "post_norm_init",
            "router_aux_loss_coef", "selection_bias_init_std", "window",
            "load_balance_coeff_as_bias_update_speed", "initializer",
            "param_dtype", "dtype", "optimizer", "data"
            } <= set(conf["assumed"])
    assert "scopes" not in conf and "kernels" not in conf
    cfg = train.program_config(conf)
    assert [(kind, n) for kind, n in cfg.kind_runs] == [
        ((S, "dense"), 1), ((S, "moe"), 2), ((F, "moe"), 1), ((S, "moe"), 1)]
    assert tuple(afmoe.kinds(conf)) == cfg.layer_kinds
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.dense_width, cfg.vocab_size, cfg.norm_eps,
            cfg.rope_theta, cfg.tie_embeddings, cfg.sliding_window) == (
                3072, 48, 8, 128, 3072, 12288, 25024, 1e-5, 10000, False,
                4096)
    assert (cfg.num_experts, cfg.local_experts, cfg.first_expert,
            cfg.num_selected, cfg.norm_topk_prob, cfg.topk_norm_eps,
            cfg.router_scoring, cfg.select_bias, cfg.routed_scaling_factor,
            cfg.shared_experts, cfg.aux_loss_coef, cfg.bias_update_speed,
            cfg.leading_dense) == (
                256, 8, 0, 4, True, 1e-20, "sigmoid", True, 2.448, 1, 0.0,
                5e-5, 1)
    assert (cfg.qk_head_norm, cfg.attn_output_gate, cfg.block_norm,
            cfg.position_embedding, cfg.rotary(True), cfg.rotary(False)) == (
                True, True, "sandwich", "rope_windowed", True, False)
    assert cfg.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert cfg.embed_init_std == pytest.approx(3072 ** -0.5)
    assert cfg.post_norm_init == pytest.approx(120 ** -0.5)
    assert afmoe.layer_kwargs(conf)["window"] == 4096
    bench = _load(os.pardir, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == list(conf["reduced"]) == list(CUT)
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


def test_the_cell_its_job_and_its_metrics():
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert len(bench["workloads"]) >= 11   # found by name: later cells pass
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-share-1x8192", 1)
    assert len(cell["why"]) <= 200
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"], job["warmup_steps"], job["traced_steps"]) == (
                "train", 1, 8192, None, 1, 2, 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # the cell's MEMBERSHIP in its three lists, which it opened and later
    # windowed cells append to (PR 55's did; PR 62 holds it so)
    for name in METRICS:
        assert per_layer[name]["workloads"][0] == CELL
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS
    for name in METRICS:
        assert per_layer[name] == {
            "name": name, "unit": per_layer[name]["unit"],
            "better": "higher" if "roofline" in name else "lower",
            "source": ("program_counter" if "executed" in name
                       else "device_trace"),
            "layer": per_layer["flash_roofline"]["layer"],
            "moves": "train_tokens_per_s",
            "workloads": [CELL] + per_layer[name]["workloads"][1:]}
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    # at least these: a later PR may list the cell on an entry of its own
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} >= set(METRICS + APPENDED_TO)
    # both four-chip places were taken: this one is a one-chip cell
    upto = bench["workloads"][:[c["name"] for c in bench["workloads"]
                                ].index(CELL) + 1]
    assert sum(c["chips"] == 4 for c in upto) == 2 == len(upto) // 4
    assert afmoe.STEP_METRICS["moe_dropped"] == ("sum", 0.0)
    assert {"moe_held_share", "moe_load_max_over_mean",
            "moe_rows_visited_share", "attn_window_executed_share"
            } <= set(afmoe.STEP_METRICS)


def _whole(conf):
    whole = dict(conf, **{k: published for k, (published, _) in CUT.items()})
    whole.pop("reduced")
    return whole


@pytest.mark.parametrize("whole,total", [(False, 1603994880),
                                         (True, 398635286016)],
                         ids=["the-share", "published"])
def test_the_parameter_count_is_init_params(whole, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), of the share (ISSUE 53's
    1604.0 M with the norms and biases) and of the published model (398.6 B)."""
    from ray_tpu.models.llama import init_params

    conf = _whole(_conf()) if whole else _conf()
    assert flops_afmoe.total_params(conf) == total
    if not whole:   # the program's fields need the file's ``reduced``
        shapes = jax.eval_shape(
            lambda k: init_params(k, train.program_config(conf)),
            jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == total


def test_flops_count_the_windows_pairs_not_the_causal_ones():
    """ISSUE 53's arithmetic: 41 TFLOP a step, attention 9.9 of them; a
    windowed layer's 25.17 M pairs a head of the 33.56 M causal ones."""
    conf = _conf()
    assert flops.of(conf) is flops_afmoe and flops.counts_experts(conf)
    assert (flops_afmoe.windowed_layers(conf), flops_afmoe.full_layers(conf),
            flops_afmoe.expert_layers(conf)) == (4, 1, 4)
    assert flops_afmoe.window_pairs(conf, 8192) == WINDOW_PAIRS == 25167872
    assert flops_afmoe.causal_pairs(8192) == CAUSAL_PAIRS == 33558528
    # at the window's length and below the window IS the causal mask
    assert flops_afmoe.window_pairs(conf, 4096) == 4096 * 4097 // 2
    assert flops_afmoe.window_pairs(conf, 512) == 512 * 513 // 2
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024
    dense, expert = 3 * 3072 * 12288, 3 * 3072 * 3072
    assert (attention, dense, expert) == (62914560, 113246208, 28311552)
    assert flops_afmoe.held_per_token(conf) == 0.125        # 4 x 8 / 256
    matmul = (5 * attention + dense
              + 4 * (3072 * 256 + 1.125 * expert) + 3072 * 25024)
    assert flops_afmoe.active_matmul_params(conf) == matmul
    pair = 12 * 48 * 128
    assert flops_afmoe.window_step_flops(conf, 1, 8192) == \
        pair * 4 * WINDOW_PAIRS
    flash = pair * (4 * WINDOW_PAIRS + CAUSAL_PAIRS)
    assert flops_afmoe.flash_step_flops(conf, 1, 8192) == flash
    assert flash == pytest.approx(9.90e12, rel=1e-3)
    per_token = flops_afmoe.train_flops_per_token(conf, 8192)
    assert per_token == 6 * matmul + flash / 8192
    assert per_token * 8192 == pytest.approx(41.1e12, rel=5e-3)
    # had the causal pairs been counted, attention would read a quarter more
    assert pair * 5 * CAUSAL_PAIRS / flash == pytest.approx(1.25, abs=5e-3)
    # two rows are two sequences, each under its own window
    assert flops_afmoe.flash_step_flops(conf, 2, 8192) == 2 * flash
    layer = 6 * 8192 * (48 + 8) * 128 * 2
    assert flops_afmoe.window_step_bytes(conf, 1, 8192) == 4 * layer
    assert flops_afmoe.flash_step_bytes(conf, 1, 8192) == 5 * layer
    assert flops.roofline_seconds(
        flops_afmoe.window_step_flops(conf, 1, 8192),
        flops_afmoe.window_step_bytes(conf, 1, 8192), PEAK) == {
            "seconds": pair * 4 * WINDOW_PAIRS / 197e12, "bound": "compute"}
    # the grouped products over the rows HELD: 1024 of 32768 a layer
    assert flops_afmoe.experts_step_flops(conf, 1, 8192) == \
        6 * 8192 * 4 * 0.125 * expert
    rows, weights = 9 * 1024 * (3072 + 3072) * 2, 3 * 8 * expert * 2
    assert flops_afmoe.experts_step_bytes(conf, 1, 8192) == 4 * (
        rows + weights)
    # at the published depth and experts attention is 45 windowed : 15 full
    whole = _whole(conf)
    assert (flops_afmoe.windowed_layers(whole),
            flops_afmoe.full_layers(whole),
            flops_afmoe.held_per_token(whole)) == (45, 15, 4.0)


def _planes(win="_win"):
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops: the windowed kernels in their three passes, the
    plain ones of the full layer, the projections, the expert scopes, the
    shared expert, the layer scan, the head, the optimizer, one bare op."""
    fusion = ('%fusion.{i} = bf16[8192,3072]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[8192,3072]{{1,0}} %p.{i}), kind=kLoop')
    keys = ("qkv", "attn_out", "ffn", "route", "dispatch", "combine",
            "while", "head", "opt", "bare")
    texts = {k: fusion.format(i=i) for i, k in enumerate(keys)}
    call = ('%closed_call.{i} = bf16[1,48,8192,128]{{3,2,1,0:T(8,128)(2,1)}} '
            'custom-call(bf16[1,48,8192,128]{{3,2,1,0}} %fusion.9{i}), '
            'custom_call_target="tpu_custom_call"')
    kernels = ("fwd_w", "dq_w", "dkv_w", "fwd", "dq", "dkv", "gmm")
    texts.update({k: call.format(i=i) for i, k in enumerate(kernels)})
    layer = "jit(step)/jvp(while)/body/checkpoint/"
    back = "jit(step)/transpose(jvp(while))/body/transpose(jvp(attention))/"
    stacks = {
        "qkv": layer + "attn_qkv/dot_general",
        "fwd_w": layer + "attention/flash_fwd" + win,
        "dq_w": back + "flash_dq" + win,
        "dkv_w": back + "flash_dkv" + win,
        "fwd": layer + "attention/flash_fwd",
        "dq": back + "flash_dq",
        "dkv": back + "flash_dkv",
        "attn_out": layer + "attn_out/dot_general",
        "ffn": layer + "ffn/dot_general",
        "route": layer + "moe_route/dot_general",
        "dispatch": layer + "moe_dispatch/gather",
        "gmm": layer + "moe_experts/moe_gmm",
        "combine": layer + "moe_combine/gather",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
    }
    spans = [("qkv", 100), ("fwd_w", 60), ("dq_w", 80), ("dkv_w", 100),
             ("fwd", 20), ("dq", 30), ("dkv", 30), ("attn_out", 40),
             ("ffn", 100), ("route", 20), ("dispatch", 40), ("gmm", 60),
             ("combine", 40), ("while", 20), ("head", 90), ("opt", 50),
             ("bare", 20)]
    ops, mods = [], []
    for i in range(3):
        start = 1000 * i
        mods.append((f"jit_step({i})", start, start + 1000))
        for key, ns in spans:
            ops.append((texts[key], start, start + ns))
            start += ns
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods},
              "/host:CPU": {"python": []}}
    return planes, {"/device:TPU:0": {texts[k]: stacks[k] for k in texts}}


def _run(trace, conf, **step_metrics):
    return {"worker": {"trace": trace,
                       "window": {"step_metrics": step_metrics}},
            "conf": conf, "job": {"rows": 1, "seq": 8192}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 17000.0}}


def _trace(conf, win="_win"):
    planes, names = _planes(win)
    return trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))


def test_window_readers_on_synthetic_planes():
    conf = _conf()
    trace = _trace(conf)
    d, = trace["devices"]
    ns = 1e-9
    assert {k: pytest.approx(v) for k, v in d["kernels"].items()} == {
        "flash_fwd_win": 60 * ns, "flash_dq_win": 80 * ns,
        "flash_dkv_win": 100 * ns, "flash_fwd": 20 * ns,
        "flash_dq": 30 * ns, "flash_dkv": 30 * ns, "moe_gmm": 60 * ns}
    run = _run(trace, conf, attn_window_executed_share=1.0624)
    assert _reader("flash.window_ms").read(run) == pytest.approx(240e-6)
    # the accepted readers hold the windowed kernels with the plain ones
    assert _reader("flash.fwd_ms").read(run) == pytest.approx(80e-6)
    assert _reader("flash.dq_ms").read(run) == pytest.approx(110e-6)
    assert _reader("flash.dkv_ms").read(run) == pytest.approx(130e-6)
    assert d["flash_s"] == pytest.approx(2 * 320 * ns)
    roofline = _reader("flash.window_roofline")
    assert roofline.bound(run) == "compute"
    least = 12 * 48 * 128 * 4 * WINDOW_PAIRS / 197e12
    assert roofline.read(run) == pytest.approx(100 * least / (240 * ns))
    # windowed kernels that took 80 ms a step: 37.7 ms is the least
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["kernels"] = {
        "flash_fwd_win": 0.02, "flash_dq_win": 0.03, "flash_dkv_win": 0.03,
        "flash_fwd": 0.01}
    assert roofline.read(_run(slow, conf)) == pytest.approx(47.1, abs=0.1)
    assert _reader("flash.window_executed_share").read(run) == 1.0624
    shares = [_reader(m).read(run) or 0.0 for m in (
        "moe.time_share_pct", "step.ffn_pct", "step.attn_proj_pct",
        "step.attention_pct", "step.head_loss_pct", "step.optimizer_pct",
        "step.scan_pct", "step.unscoped_pct")]
    # with the step's idle tenth (900 ns of ops in 1000) they make 100
    assert sum(shares) == pytest.approx(90.0)
    assert _reader("step.attention_pct").read(run) == pytest.approx(32.0)


def test_on_a_program_without_the_window_the_readers_return_nothing():
    """The parent's program names no ``flash_*_win`` kernel and reports no
    ``attn_window_executed_share``, and another configuration's FLOP module
    counts no window: every reader returns None and none raises; an
    untraced run likewise."""
    conf = _conf()
    trace = _trace(conf, win="")
    mistral = _load("configs", "mistral-7b-v0.1-d4.json")
    for run in (_run(trace, conf), _run(None, conf), _run(trace, mistral),
                _run(_trace(conf), mistral)):
        got = [_reader(m).read(run) for m in METRICS]
        # the time alone reads wherever the kernels ran
        assert got[1:] == [None, None]
        assert (got[0] is None) == (
            run["worker"]["trace"] is None
            or "flash_fwd_win" not in run["worker"]["trace"]["devices"][0][
                "kernels"])
