"""The yardstick of the Olmo-Hybrid-7B cell: ``JAX_PLATFORMS=cpu python -m
pytest benchmark/tests/test_olmo_hybrid.py -q``.  Not part of tier-1."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import cuts, flops, flops_olmo_hybrid, trace_reduce
from benchmark.loops import train
from benchmark.reference import olmo_hybrid

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "olmo-hybrid-7b-d4"
CELL = "olmohybrid-train-1seq"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GDN_SCOPES = ["gdn_in", "gdn_conv", "gdn_scan", "gdn_out"]
METRICS = ["gdn.time_share_pct", "gdn.scan_ms", "gdn.conv_ms",
           "gdn.scan_roofline"]
KERNEL_MS = "gdn.kernel_ms"     # PR 42's, this cell's fifth


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
        "num_hidden_layers"}
    assert list(conf["reduced"]) == ["num_hidden_layers"]
    assert (conf["num_hidden_layers"], len(conf["layer_types"])) == (4, 32)
    assert conf["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    assert cuts.period(conf["layer_types"]) == 4
    # what the public file does not state is explained, a key each
    assert {"head_dim", "block_norm", "qk_norm", "position_embedding_type",
            "linear_conv_bias", "param_dtype", "dtype", "optimizer", "data"
            } <= set(conf["assumed"])
    assert conf["scopes"] == GDN_SCOPES and "kernels" not in conf
    cfg = train.program_config(conf)
    assert cfg.layer_runs == (("linear_attention", 3), ("full_attention", 1))
    assert (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.norm_eps, cfg.tie_embeddings
            ) == (3840, 30, 30, 128, 11008, 100352, 1e-6, False)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv,
            cfg.gdn_neg_eigval, cfg.gdn_conv_dim) == (
                30, 96, 192, 4, True, 11520)
    # no bias on the convolution: explained, and no key that the program reads
    assert "linear_conv_bias" not in conf
    assert (cfg.position_embedding, cfg.qk_norm, cfg.block_norm) == (
        "nope", True, "output")
    bench = _load(os.pardir, "BENCHMARK.json")
    cell, = [c for c in bench["workloads"] if c["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "train-1x4096", 1)
    job = _load("jobs", cell["traffic"] + ".json")
    assert (job["loop"], job["rows"], job["seq"], job["mesh"],
            job["check_rows"]) == ("train", 1, 4096, None, 1)
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]] == METRICS + [KERNEL_MS]
    # a one-chip cell (above); how many cells may take four is ONE rule:
    # test_benchmark.py::test_at_most_a_quarter_of_the_cells_take_four_chips
    assert olmo_hybrid.STEP_METRICS == {"gdn_state_absmax": ("max", None)}


@pytest.mark.parametrize("depth,total", [(4, 1603227636), (32, 7430870688)],
                         ids=["the-cut", "published-depth"])
def test_the_parameter_count_is_init_params(depth, total):
    """The FLOP module's count against the shapes ``init_params`` would
    make (``eval_shape``: nothing is allocated), at the cut and at the
    published depth, 7.43 B."""
    from ray_tpu.models.llama import init_params

    conf = dict(_conf(), num_hidden_layers=depth)
    shapes = jax.eval_shape(
        lambda k: init_params(k, train.program_config(conf)),
        jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    assert flops_olmo_hybrid.total_params(conf) == held == total


def test_flops_against_hand_counts():
    """ISSUE 38's arithmetic: ONE attention layer in four although the
    file lists eight and ``flops.py`` would count all 32 entries."""
    conf = _conf()
    assert flops.of(conf) is flops_olmo_hybrid
    assert flops.attention_layers(conf) == 32     # the trap: "attention" in
    assert (flops_olmo_hybrid.attention_layers(conf),
            flops_olmo_hybrid.linear_layers(conf)) == (1, 3)
    mlp = 3 * 3840 * 11008
    linear = 3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840
    full = 4 * 3840 * 3840
    assert (linear + 4 * 11520, full, mlp) == (88750080, 58982400, 126812160)
    matmul = 3 * linear + full + 4 * mlp + 3840 * 100352
    assert flops_olmo_hybrid.matmul_params(conf) == matmul == 1217694720
    causal = 6 * 4096 * 30 * 128
    rule = 18 * 3 * 30 * 96 * 192
    assert flops_olmo_hybrid.gdn_flops_per_token(conf) == rule
    per_token = flops_olmo_hybrid.train_flops_per_token(conf, 4096)
    assert per_token == 6 * matmul + causal + rule
    assert per_token == pytest.approx(7.430e9, rel=1e-3)
    assert 6 * 3840 * 100352 / per_token == pytest.approx(0.311, abs=1e-3)
    whole = dict(conf, num_hidden_layers=32)
    assert (flops_olmo_hybrid.attention_layers(whole),
            flops_olmo_hybrid.linear_layers(whole)) == (8, 24)
    assert 6 * 3840 * 100352 / flops_olmo_hybrid.train_flops_per_token(
        whole, 4096) == pytest.approx(0.053, abs=1e-3)
    # the rule's own counts: 122 GFLOP and 1.14 GB a step, bound by memory
    assert flops_olmo_hybrid.gdn_step_flops(conf, 1, 4096) == rule * 4096
    qk, v, gates = 2 * 4096 * 2880 * 2, 4096 * 5760 * 2, 2 * 4096 * 30 * 4
    assert flops_olmo_hybrid.gdn_step_bytes(conf, 1, 4096) == 3 * (
        (qk + 2 * v + gates) + (2 * qk + 3 * v + 2 * gates)) == 1141309440
    assert flops.roofline_seconds(
        flops_olmo_hybrid.gdn_step_flops(conf, 1, 4096),
        flops_olmo_hybrid.gdn_step_bytes(conf, 1, 4096), PEAK) == {
            "seconds": 1141309440 / 819e9, "bound": "memory"}
    # flash: one MHA layer at head size 128
    assert flops_olmo_hybrid.flash_step_flops(conf, 1, 4096) == \
        6 * 4096 ** 2 * 30 * 128
    assert flops_olmo_hybrid.flash_step_bytes(conf, 1, 4096) == \
        12 * 4096 * 30 * 128 * 2


def _planes():
    """Three executions of the step (the first a lead-in), each 1000 ns
    with 900 ns of ops: the four delta-rule scopes, the dense FFN, the
    layer scan, the flash kernel, the head, the optimizer, one bare op."""
    fusion = ('%fusion.{i} = bf16[4096,3840]{{1,0:T(8,128)(2,1)}} fusion('
              'bf16[4096,3840]{{1,0}} %p.{i}), kind=kLoop')
    texts = {k: fusion.format(i=i) for i, k in enumerate(
        ("in", "conv", "scan_f", "scan_r", "scan_b", "out", "ffn", "while",
         "head", "opt", "bare"))}
    texts["flash"] = (
        '%closed_call.3 = (bf16[1,30,4096,128]{3,2,1,0:T(8,128)(2,1)}, '
        'f32[1,30,4096,128]{3,2,1,0:T(8,128)}) custom-call(bf16[1,30,4096,'
        '128]{3,2,1,0} %fusion.99), custom_call_target="tpu_custom_call"')
    stacks = {
        "in": "jit(step)/jvp(while)/body/checkpoint/gdn_in/dot_general",
        "conv": "jit(step)/jvp(while)/body/checkpoint/gdn_conv/mul",
        "scan_f": "jit(step)/jvp(while)/body/checkpoint/gdn_scan/while/body/"
                  "dot_general",
        "scan_r": "jit(step)/transpose(jvp(while))/body/checkpoint/"
                  "rematted_computation/gdn_scan/exp",
        "scan_b": "jit(step)/transpose(jvp(while))/body/transpose(jvp("
                  "gdn_scan))/dot_general",
        "out": "jit(step)/jvp(while)/body/checkpoint/gdn_out/dot_general",
        "ffn": "jit(step)/jvp(while)/body/checkpoint/ffn/dot_general",
        "while": "jit(step)/jvp(while)/body/dynamic_slice",
        "head": "jit(step)/jvp(lm_head)/dot_general",
        "opt": "jit(step)/optimizer/add",
        "bare": "jit(step)/convert_element_type",
        "flash": "jit(step)/jvp(while)/body/checkpoint/attention/flash_fwd",
    }
    spans = [("in", 100), ("conv", 30), ("scan_f", 40), ("scan_r", 40),
             ("scan_b", 70), ("out", 60), ("ffn", 250), ("while", 20),
             ("flash", 50), ("head", 150), ("opt", 70), ("bare", 20)]
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


def _run(trace, conf):
    return {"worker": {"trace": trace, "window": {"step_metrics": {}}},
            "conf": conf, "job": {"rows": 1, "seq": 4096}, "chips": 1,
            "peak": PEAK, "end_to_end": {"train_tokens_per_s": 11000.0}}


def test_gdn_readers_and_the_sum_to_a_hundred_on_synthetic_planes():
    """The rule's own ``lax.scan`` is a ``while`` UNDER ``gdn_scan``: the
    scope comes first on the name stack and takes it, not the row
    ``scan``."""
    conf = _conf()
    planes, names = _planes()
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=conf.get("kernels", ()))
    d, = trace["devices"]
    ns = 1e-9
    assert d["scopes"]["gdn_scan"] == {
        "forward": pytest.approx(40 * ns), "remat": pytest.approx(40 * ns),
        "backward": pytest.approx(70 * ns)}
    assert d["scopes"]["gdn_in"] == {"forward": pytest.approx(100 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    run = _run(trace, conf)
    assert _reader("gdn.time_share_pct").read(run) == pytest.approx(34.0)
    assert _reader("gdn.scan_ms").read(run) == pytest.approx(150e-6)
    assert _reader("gdn.conv_ms").read(run) == pytest.approx(30e-6)
    roofline = _reader("gdn.scan_roofline")
    assert roofline.bound(run) == "memory"
    assert roofline.read(run) == pytest.approx(
        100 * (1141309440 / 819e9) / (150 * ns))
    # a step whose rules took 30 ms reads in single digits, under 100
    slow = json.loads(json.dumps(trace))
    slow["devices"][0]["scopes"]["gdn_scan"] = {"forward": 0.030}
    assert 0 < roofline.read(_run(slow, conf)) < 10
    shares = [_reader(m).read(run) or 0.0 for m in (
        "gdn.time_share_pct", "step.ffn_pct", "step.attn_proj_pct",
        "step.attention_pct", "step.head_loss_pct", "step.optimizer_pct",
        "step.scan_pct", "step.unscoped_pct")]
    # with the step's idle tenth (900 ns of ops in 1000) they make 100
    assert sum(shares) == pytest.approx(90.0)


def test_on_a_program_without_the_scopes_the_readers_return_nothing():
    """The parent's program opens no ``gdn_*`` scope, and another
    configuration's FLOP module counts no delta rule: every reader
    returns None and none raises; an untraced run likewise."""
    conf = _conf()
    planes, names = _planes()
    names = {plane: {text: stack.replace("gdn_", "xyz_")
                     for text, stack in stacks.items()}
             for plane, stacks in names.items()}
    trace = trace_reduce.reduce_planes(
        planes, step_module="jit_step", annotations=(), names=names,
        scopes=conf["scopes"], kernels=())
    for run in (_run(trace, conf), _run(None, conf),
                _run(trace, _load("configs", "mistral-7b-v0.1-d4.json"))):
        assert [_reader(m).read(run) for m in METRICS] == [None] * 4


def test_the_entries_this_cell_appends_leave_the_older_ones_as_they_were():
    """Entries are only ever appended: PR 36's seven (which
    ``test_host_clock_readers.py::test_entries_in_benchmark_json`` finds by
    name since PR 52) stand each in place, in order, right before this
    cell's four, whatever later PRs appended after those; their source,
    direction, keys, layer and end-to-end metric; no ``workloads`` key, so
    this cell reports the five that move what it reports."""
    NAMES = ("compile.trace_s", "compile.lower_s", "compile.backend_s",
             "compile.cache_load_s", "compile.programs",
             "host.step_max_over_median", "host.gc_pause_ms")
    MOVES = {"compile": ("compile cache", "setup_s"),
             "host": ("host loop", "train_tokens_per_s")}
    entries = _load(os.pardir, "BENCHMARK.json")["per_layer"]
    names = [m["name"] for m in entries]
    first = names.index(NAMES[0])
    assert names[first:first + len(NAMES)] == list(NAMES)
    after = names[first + len(NAMES):]
    assert after[:len(METRICS)] == METRICS and KERNEL_MS in after
    for m in entries[first:first + len(NAMES)]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        assert (m["source"], m["better"]) == ("program_counter", "lower")
        assert (m["layer"], m["moves"]) == MOVES[m["name"].split(".")[0]]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
