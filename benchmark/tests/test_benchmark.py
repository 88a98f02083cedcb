"""Checks of the yardstick itself, on the CPU.  Nothing here is a device
number: the rehearsal asserts counts and shapes of the result only."""

import json
import os
import statistics
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

from benchmark import cuts, flops, trace_reduce, trace_scopes  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _reader(metric):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_m", os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# A configuration file in the public key names, at CPU size.
TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "vocab_size": 256,
    "assumed": {"param_dtype": {"value": "float32"},
                "dtype": {"value": "float32"}},
    "reference": "decoder", "flops": "flops",
    "check": {"token_nll_rms": 0.012},
    "llama_config": _conf("mistral-7b-v0.1-d4")["llama_config"],
}
# The same for the expert layer: the OLMoE file's own keys at CPU widths.
TINY_MOE = dict(
    _conf("olmoe-1b-7b-0125-1chip"), hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=32, vocab_size=256,
    num_experts=8, num_experts_per_tok=3, num_hidden_layers=2)
# TINY as one chip's slice of a vocabulary of 2048 rows, eight chips a layer.
SLICED = dict(
    TINY, deployment="made up: eight chips share each layer",
    share={"chips_per_layer": 8, "how": "rows of the embedding and the head"},
    reduced={"vocab_size": {"kind": "vocabulary", "published": 2048,
                            "run": 256, "why": "made up"}})


# ------------------------------------------------------------- flops.py --

@pytest.mark.parametrize("name,seq,matmul,total,attention", [
    # by hand: layers x (q + k + v + o + 3 FFN) + head; + embedding + norms
    ("mistral-7b-v0.1-d4", 4096,
     4 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336)
     + 4096 * 32000,
     1_134_596_096, 6 * 4 * 4096 * 32 * 128),
    ("deepseek-llm-7b-d20-x4", 4096,
     20 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400,
     4_886_532_096, 6 * 20 * 4096 * 32 * 128),
])
def test_flops_against_hand_counts(name, seq, matmul, total, attention):
    conf = _conf(name)
    assert flops.of(conf) is flops
    assert flops.matmul_params(conf) == matmul
    assert flops.total_params(conf) == total
    assert flops.attention_flops_per_token(conf, seq) == attention
    assert flops.train_flops_per_token(conf, seq) == 6 * matmul + attention


def test_flash_roofline_names_its_bound():
    conf, peak = _conf("mistral-7b-v0.1-d4"), {"bf16_flops_per_s": 197e12,
                                               "hbm_bytes_per_s": 819e9}
    # one layer-row at s=4096: 6 s^2 h d FLOPs against 12 tensors' bytes
    f = flops.flash_step_flops(conf, 1, 4096)
    b = flops.flash_step_bytes(conf, 1, 4096)
    assert f == 4 * 6 * 4096 ** 2 * 32 * 128
    assert b == 4 * 6 * 4096 * (32 + 8) * 128 * 2
    assert flops.roofline_seconds(f, b, peak)["bound"] == "compute"
    # with 8 KV heads, s=512 is 204.8 FLOP/byte, under the chip's 240.5
    short = flops.roofline_seconds(flops.flash_step_flops(conf, 32, 512),
                                   flops.flash_step_bytes(conf, 32, 512),
                                   peak)
    assert short["bound"] == "memory"


def test_one_attention_layer_in_ten_is_counted_once():
    """A configuration with ``layer_types`` (nine ``mamba`` layers and one
    ``attention`` layer, as granite-4.0-h has them) has ONE layer of causal
    attention for ``flash_roofline`` and ``train_step.mfu_pct`` to count."""
    dense = dict(_conf("mistral-7b-v0.1-d4"), num_hidden_layers=10)
    hybrid = dict(dense, layer_types=["mamba"] * 5 + ["attention"]
                  + ["mamba"] * 4)
    assert flops.attention_layers(dense) == 10
    assert flops.attention_layers(hybrid) == 1
    assert flops.attention_layers(
        dict(dense, layer_types=["full_attention", "linear_attention"])) == 2
    assert flops.flash_step_flops(hybrid, 4, 4096) == \
        flops.flash_step_flops(dense, 4, 4096) / 10
    assert flops.flash_step_bytes(hybrid, 4, 4096) == \
        flops.flash_step_bytes(dense, 4, 4096) / 10
    # the readers, on a step whose one flash layer took 10 ms
    def run(conf):
        device = {"steps": 2, "flash_s": 0.020, "step_s": [0.5, 0.5]}
        return {"conf": conf, "job": {"rows": 4, "seq": 4096}, "chips": 1,
                "peak": PEAK, "worker": {"trace": {"devices": [device]}},
                "end_to_end": {"train_tokens_per_s": 20000.0}}

    roofline = _reader("flash_roofline").read
    assert roofline(run(hybrid)) == pytest.approx(roofline(run(dense)) / 10)
    assert roofline(run(hybrid)) == pytest.approx(
        100 * 6 * 4 * 4096 ** 2 * 32 * 128 / 197e12 / 0.010)
    mfu = _reader("train_step.mfu_pct").read
    per_layer_attention = 6 * 4096 * 32 * 128
    projections = 6 * (2 * 4096 * 4096 + 2 * 4096 * 1024)
    assert mfu(run(dense)) - mfu(run(hybrid)) == pytest.approx(
        100 * 20000.0 * 9 * (per_layer_attention + projections) / 197e12)


CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


def _published(name):
    with open(os.path.join(BENCH, "testdata", "published",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_published_widths_and_reduced_keys(name):
    """Every file under ``configs/`` against the copy of its public
    ``config.json`` under ``testdata/published/``, by the rule of
    ``benchmark/cuts.py``: equal key for key, except the keys it lists
    under ``reduced``, each of a kind the rule knows; no number left out;
    nothing added that is not the benchmark's own or explained under
    ``assumed``; the modules it names are there."""
    conf = _conf(name)
    assert cuts.complaints(conf, _published(name)) == []
    # PR 33 widened the rule and left the older files as they were: an entry
    # of ``reduced`` that names no kind is the depth, and a file that states
    # no share cuts the depth (and a list with it) alone
    for key, cut in conf["reduced"].items():
        if "kind" not in cut:
            assert key == conf["llama_config"]["num_layers"], key
        if "share" not in conf:
            assert cut.get("kind", "depth") in ("depth", "pattern"), key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {c["name"]: c for c in json.load(f)["configs"]}
    if name in entries:
        assert entries[name]["reduced"] == list(conf["reduced"])
        assert entries[name]["source"] == conf["source"]
        assert entries[name]["file"] == f"benchmark/configs/{name}.json"


# A made-up public ``config.json`` at sizes a test can hold: 64 routed
# experts, 2 leading dense layers, a ``layer_types`` of period 4.
PUBLIC = {
    "first_k_dense_replace": 2, "hidden_size": 64, "intermediate_size": 128,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 4,
    "moe_intermediate_size": 32, "n_routed_experts": 64,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 16, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "vocab_size": 4096,
}
PERIOD_8 = (["sliding_attention"] * 7 + ["full_attention"]) * 2


def _cut(public, reduced, **own):
    """A configuration file cut from ``public``: ``reduced`` is key ->
    (kind, run), the kind None for an entry that names none; ``own`` sets
    further keys of the file (None takes one out)."""
    conf = dict(
        public, source="made up", reference="decoder", flops="flops",
        assumed=TINY["assumed"], check=TINY["check"],
        deployment="made up: eight chips share each layer",
        share={"chips_per_layer": 8, "how": "experts and vocabulary rows",
               "leading_dense": "first_k_dense_replace"},
        llama_config={"vocab_size": "vocab_size",
                      "num_layers": "num_hidden_layers",
                      "head_dim": "hidden_size/num_attention_heads",
                      "num_experts": "n_routed_experts@published",
                      "experts_held": "n_routed_experts"},
        reduced={})
    for key, (kind, run) in reduced.items():
        conf[key] = run
        conf["reduced"][key] = {"published": public[key], "run": run,
                                "why": "made up"}
        if kind:
            conf["reduced"][key]["kind"] = kind
    for key, value in own.items():
        conf[key] = value
        if value is None:
            del conf[key]
    return conf


DEPTH_6 = {"num_hidden_layers": ("depth", 6),
           "layer_types": ("pattern", PUBLIC["layer_types"][:6])}
SHARE = dict(DEPTH_6, n_routed_experts=("experts_held", 8),
             vocab_size=("vocabulary", 512))


NO_LIST = {k: v for k, v in PUBLIC.items() if k != "layer_types"}
# the same leading dense layers as other public files say them
OTHER_NAME = dict({k: v for k, v in NO_LIST.items()
                   if k != "first_k_dense_replace"}, moe_layer_start_index=2)
MLP_TYPES = dict(OTHER_NAME, mlp_layer_types=["dense"] * 2 + ["sparse"] * 14)
EXPERTS_5 = dict(n_routed_experts=("experts_held", 8),
                 num_hidden_layers=("depth", 5))
KEEPS_4 = "; a share keeps at least 4"


def _shared(**more):
    return {"share": dict({"chips_per_layer": 8, "how": "experts"}, **more)}


# ONE leading dense layer of the two, four layers after it
DENSE_1 = {"num_hidden_layers": ("depth", 5),
           "layer_types": ("pattern", PUBLIC["layer_types"][:5]),
           "first_k_dense_replace": ("leading_dense", 1)}
ONE_OF_TWO = dict(SHARE, **DENSE_1)
NAMES_THE_KEY = "kind leading_dense is for the key that the file's " \
    "share.leading_dense names "
MLP_ONLY = dict(OTHER_NAME, mlp_only_layers=[0, 1])
# 256 experts over 32 chips, the vocabulary's rows over 8 of them
PUBLIC_256 = dict(PUBLIC, n_routed_experts=256)


def _over(n):
    return {"share": {"chips_per_layer": 32, "vocabulary_over": n,
                      "how": "experts over 32; rows over the 8 of a host",
                      "leading_dense": "first_k_dense_replace"}}


@pytest.mark.parametrize("public,reduced,own,complaint", [
    (PUBLIC, {"num_hidden_layers": (None, 4)}, {"share": None}, None),
    (PUBLIC, SHARE, {}, None),
    (dict(PUBLIC, n_routed_experts=56),
     dict(SHARE, n_routed_experts=("experts_held", 7)), {},
     "reduced[n_routed_experts]: 7 experts held"),
    (PUBLIC, dict(DEPTH_6, vocab_size=("vocabulary", 256)),
     {"share": {"chips_per_layer": 16, "how": "vocabulary rows"}},
     "reduced[vocab_size]: 256 rows are under an eighth"),
    (NO_LIST, EXPERTS_5, {},
     "reduced[num_hidden_layers]: 3 layers after the 2 leading dense ones"
     + KEEPS_4),
    (OTHER_NAME, EXPERTS_5, _shared(leading_dense="moe_layer_start_index"),
     "reduced[num_hidden_layers]: 3 layers after the 2 leading dense ones"
     + KEEPS_4),
    (MLP_TYPES, dict(EXPERTS_5, mlp_layer_types=(
        "pattern", MLP_TYPES["mlp_layer_types"][:5])),
     _shared(leading_dense="mlp_layer_types"),
     "reduced[num_hidden_layers]: 3 layers after the 2 leading dense ones"
     + KEEPS_4),
    (OTHER_NAME, EXPERTS_5, _shared(),
     "share: a file with experts held states leading_dense"),
    (OTHER_NAME, EXPERTS_5, _shared(leading_dense="first_k_dense_replace"),
     "share: leading_dense names 'first_k_dense_replace', which is no"),
    (OTHER_NAME, EXPERTS_5, _shared(leading_dense=None), None),
    (dict(PUBLIC, layer_types=PERIOD_8),
     dict(SHARE, layer_types=("pattern", PERIOD_8[:6])), {},
     "reduced[num_hidden_layers]: 4 layers after the 2 leading dense ones "
     "are not a whole period of layer_types (8)"),
    (PUBLIC, dict(SHARE, vocab_size=("vocabulary", 1024)),
     {"share": {"chips_per_layer": 4, "how": "half the experts",
                "leading_dense": "first_k_dense_replace"}},
     "reduced[n_routed_experts]: run 8 x chips_per_layer 4 is not the "
     "published 64"),
    (PUBLIC, SHARE, {"hidden_size": 32},
     "hidden_size: differs from the published file and is not listed"),
    (PUBLIC, dict(SHARE, hidden_size=("depth", 32)), {},
     "reduced[hidden_size]: kind depth is for the key that llama_config "
     "gives the program as num_layers (num_hidden_layers); no other count "
     "and no width is ever cut"),
    (PUBLIC, dict(SHARE, num_attention_heads=(None, 2)), {},
     "reduced[num_attention_heads]: kind depth is for the key that"),
    (PUBLIC, {"n_routed_experts": (None, 2)},
     {"share": None, "deployment": None},
     "reduced[n_routed_experts]: kind depth is for the key that"),
    (PUBLIC, {"n_routed_experts": ("experts_held", 2)},
     {"share": None, "deployment": None},
     "share: a file with experts held or a vocabulary slice states"),
    (PUBLIC, dict(SHARE, num_attention_heads=("experts_held", 2)), {},
     "reduced[num_attention_heads]: kind experts_held is for the key that "
     "counts the routed experts"),
    (PUBLIC, dict(SHARE, num_experts_per_tok=("experts_held", 2)), {},
     "reduced[num_experts_per_tok]: kind experts_held is for the key that"),
    (PUBLIC, dict(SHARE, num_key_value_heads=("vocabulary", 1)), {},
     "reduced[num_key_value_heads]: kind vocabulary is for vocab_size"),
    (PUBLIC, SHARE, {"share": None},
     "share: a file with experts held or a vocabulary slice states"),
    (PUBLIC, SHARE, {"deployment": None},
     "deployment: a share states the deployment"),
    (PUBLIC, dict(SHARE, layer_types=("pattern",
                                      PUBLIC["layer_types"][1:7])), {},
     "reduced[layer_types]: run is not the first 6 entries"),
    (PUBLIC, ONE_OF_TWO, {}, None),
    (PUBLIC, dict(SHARE, first_k_dense_replace=("leading_dense", 0)), {},
     "reduced[first_k_dense_replace]: kind leading_dense is for whole "
     "numbers, run from 1 to published"),
    (dict(PUBLIC, moe_layer_start_index=2),
     dict(SHARE, moe_layer_start_index=("leading_dense", 1)), {},
     "reduced[moe_layer_start_index]: " + NAMES_THE_KEY
     + "(first_k_dense_replace)"),
    (PUBLIC, dict(SHARE, hidden_size=("leading_dense", 32)), {},
     "reduced[hidden_size]: " + NAMES_THE_KEY + "(first_k_dense_replace)"),
    (MLP_TYPES, dict(EXPERTS_5, num_hidden_layers=("depth", 6),
                     mlp_layer_types=("leading_dense",
                                      MLP_TYPES["mlp_layer_types"][1:])),
     _shared(leading_dense="mlp_layer_types"),
     "reduced[mlp_layer_types]: kind leading_dense is for whole numbers"),
    (MLP_ONLY, dict(EXPERTS_5, num_hidden_layers=("depth", 6),
                    mlp_only_layers=("leading_dense", [0])),
     _shared(leading_dense="mlp_only_layers"),
     "reduced[mlp_only_layers]: kind leading_dense is for whole numbers"),
    (PUBLIC, DENSE_1, {"share": None, "deployment": None},
     "reduced[first_k_dense_replace]: " + NAMES_THE_KEY + "(None), in a "
     "file that states a share"),
    (PUBLIC_256, SHARE, _over(8), None),
    (PUBLIC_256, dict(SHARE, vocab_size=("vocabulary", 256)), _over(16),
     "reduced[vocab_size]: 256 rows are under an eighth"),
    (PUBLIC_256, SHARE, _over(3),
     "share: vocabulary_over 3 is no whole number from 2 that divides "
     "chips_per_layer 32"),
    (PUBLIC_256, dict(SHARE, vocab_size=("vocabulary", 1024)), _over(8),
     "reduced[vocab_size]: run 1024 x vocabulary_over 8 is not the "
     "published 4096"),
], ids=["depth-alone", "one-chips-share", "seven-experts-held",
        "a-sixteenth-of-the-vocabulary", "three-layers-after-the-dense-ones",
        "dense-layers-under-another-name", "dense-layers-in-a-per-layer-list",
        "experts-held-and-no-leading-dense", "leading-dense-names-no-key",
        "no-dense-layers-said-so",
        "half-a-period", "run-times-chips-is-not-published",
        "a-width-changed-and-not-listed", "a-width-listed",
        "heads-as-depth", "experts-as-depth-and-no-share",
        "experts-held-and-no-share", "heads-as-experts-held",
        "experts-a-token-as-experts-held", "heads-as-vocabulary",
        "no-share-key", "no-deployment", "a-pattern-that-is-no-prefix",
        "one-dense-layer-of-two", "no-dense-layer-kept",
        "leading-dense-on-a-key-the-share-does-not-name",
        "a-width-as-leading-dense", "a-per-layer-list-as-leading-dense",
        "a-list-of-layer-numbers-as-leading-dense",
        "leading-dense-cut-and-no-share",
        "experts-over-32-and-rows-over-8", "rows-over-16",
        "rows-over-3-of-32", "rows-that-do-not-multiply-out"])
def test_the_rule_of_a_cut(public, reduced, own, complaint):
    """``cuts.complaints`` on files cut from a made-up public file: those
    that keep the rule give none, every other gives the ONE complaint that
    names its fault."""
    got = cuts.complaints(_cut(public, reduced, **own), public)
    if complaint is None:
        assert got == []
    else:
        assert len(got) == 1 and got[0].startswith(complaint), got


def test_the_rule_takes_the_catalogs_row_with_six_leading_dense_layers():
    """A catalog row under ``testdata/published`` before any configuration
    of it exists (Trinity-Large-Preview: 6 leading dense layers, 256
    experts, 200192 rows), cut to the guide's floors as the driver counts
    them: ONE leading dense layer and 4 after it, 8 experts = one chip of
    32, an eighth of the vocabulary.  The two module names are stubbed."""
    public = _published("trinity-large-preview")
    assert (public["num_dense_layers"], public["num_experts"],
            public["num_hidden_layers"], cuts.period(
                public["layer_types"][6:])) == (6, 256, 60, 4)
    share = {"chips_per_layer": 32, "vocabulary_over": 8,
             "leading_dense": "num_dense_layers",
             "how": "experts over 32 chips, 8 a chip; the rows over the 8 "
                    "chips of a group; the layers left out as stages"}
    cut = {"num_hidden_layers": ("depth", 5),
           "num_dense_layers": ("leading_dense", 1),
           "num_experts": ("experts_held", 8),
           "vocab_size": ("vocabulary", 25024)}

    def conf(reduced, **share_keys):
        made = _cut(public, reduced, share=dict(share, **share_keys))
        made["llama_config"] = {"num_layers": "num_hidden_layers"}
        return made

    assert cuts.complaints(conf(cut), public) == []
    # all six dense layers demanded: nothing is left after them
    six = {k: v for k, v in cut.items() if k != "num_dense_layers"}
    assert cuts.complaints(conf(six), public) == [
        "reduced[num_hidden_layers]: -1 layers after the 6 leading dense "
        "ones; a share keeps at least 4",
        "reduced[num_hidden_layers]: -1 layers after the 6 leading dense "
        "ones are not a whole period of layer_types (4)"]
    # one share for experts and rows alike: a thirty-second of the rows
    del share["vocabulary_over"]
    assert cuts.complaints(conf(cut), public) == [
        "reduced[vocab_size]: run 25024 x chips_per_layer 32 is not the "
        "published 200192"]
    assert cuts.complaints(conf(dict(cut, vocab_size=("vocabulary", 6256))),
                           public) == [
        "reduced[vocab_size]: 6256 rows are under an eighth of the "
        "vocabulary"]


def test_the_leading_period_of_a_per_layer_list():
    assert cuts.period(_conf("granite-4.0-h-micro-d10")["layer_types"]) == 10
    assert cuts.period(PUBLIC["layer_types"][2:]) == 4
    assert cuts.period(PERIOD_8[2:]) == 8   # the second copy as far as it goes
    assert cuts.period(["sparse"] * 47) == 1
    # an irregular tail does not count; a list that never repeats is whole
    assert cuts.period(["conv", "conv", "full"] * 3 + ["conv", "full"]) == 3
    assert cuts.period(["dense"] + ["sparse"] * 5) == 6


@pytest.mark.parametrize("value,dense", [
    (2, 2), (0, 0), ([0, 1], 2), ([], 0), ([0, 1, 5], 2), ([3], 0),
    (["dense"] + ["sparse"] * 5, 1), (["sparse"] * 6, 0),
    (["dense", "dense", "sparse", "dense", "sparse"], 2),
    ("two", None), (None, None), (2.0, None)])
def test_the_leading_dense_layers_as_public_files_say_them(value, dense):
    assert cuts.leading_dense({"said_here": value}, "said_here") == dense
    assert cuts.leading_dense({}, "said_here") is None
    assert cuts.leading_dense({"said_here": value}, ["said_here"]) is None


# ------------------------------------ what the program is told: train.py --

def test_llama_config_resolves_published_and_held_counts():
    """One key gives the router its published width and the expert layer the
    count this chip holds; ``a/b`` and plain keys as before; a key the file
    does not have fails by its name.  ``experts_held`` is no field of the
    program yet: the resolution is read, no ``LlamaConfig`` built."""
    from benchmark.loops import train

    conf = _cut(PUBLIC, SHARE)
    fields = train.program_fields(conf)
    assert (fields["num_experts"], fields["experts_held"]) == (64, 8)
    assert (fields["vocab_size"], fields["num_layers"], fields["head_dim"]
            ) == (512, 6, 16)
    assert str(fields["dtype"]) == str(fields["param_dtype"]) == "float32"
    both = dict(conf, llama_config={
        "vocab_whole": "vocab_size@published", "heads": "num_attention_heads"
        "@published", "rows_a_chip": "vocab_size@published/vocab_size"})
    assert train.program_fields(both) == dict(
        vocab_whole=4096, heads=4, rows_a_chip=8, dtype=fields["dtype"],
        param_dtype=fields["param_dtype"])
    for expr, named in (("no_such_key", "no_such_key"),
                        ("no_such_key@published", "no_such_key"),
                        ("vocab_size@run", "vocab_size@run"),
                        ("hidden_size/no_such_key", "no_such_key")):
        with pytest.raises(KeyError, match=f"'{named}'"):
            train.program_fields(dict(conf, llama_config={"x": expr}))


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_gives_what_it_gave(name):
    """The resolution (a key, ``a/b``, and since PR 33 ``<key>@published``),
    written out here with the published count read from the COPY of the
    public file, builds the same ``LlamaConfig``."""
    import jax.numpy as jnp

    from benchmark.loops import train
    from ray_tpu.models.llama import LlamaConfig

    conf, published = _conf(name), _published(name)

    def value(term):
        key, at, which = term.partition("@")
        assert which == ("published" if at else ""), term
        return published[key] if at else conf[key]

    fields = {}
    for field, expr in conf["llama_config"].items():
        a, _, b = expr.partition("/")
        fields[field] = value(a) // value(b) if b else value(a)
    for k in ("dtype", "param_dtype"):
        fields[k] = jnp.dtype(conf["assumed"][k]["value"])
    assert train.program_config(conf) == LlamaConfig(**fields)
    assert train.program_fields(conf) == fields


def test_the_traffic_of_a_sliced_file_never_leaves_the_slice(monkeypatch):
    """A sliced vocabulary is a smaller vocabulary: the program is built at
    the slice, and the window's batches and the check's sample (both
    ``train.draw_tokens``) draw their ids below it."""
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params

    conf = SLICED
    assert cuts.complaints(conf, dict(conf, vocab_size=2048)) == []
    cfg = train.program_config(conf)
    assert cfg.vocab_size == 256
    assert train.program_fields(dict(conf, llama_config={
        "whole": "vocab_size@published"}))["whole"] == 2048
    batch = train.draw_tokens(np.random.default_rng([2147483653, 0]), cfg,
                              64, 128)
    assert batch.shape == (64, 129) and batch.dtype == np.int32
    assert (batch.min(), batch.max()) == (0, 255)
    drawn = []
    draw = train.draw_tokens
    monkeypatch.setattr(train, "draw_tokens", lambda *a: drawn.append(
        draw(*a)) or drawn[-1])
    job = {"rows": 4, "seq": 64, "mesh": None, "check_rows": 4}
    check = train.reference_check(
        decoder, conf, job, cfg, init_params(jax.random.PRNGKey(5), cfg),
        5, None, jax.devices()[0])
    sample, = drawn
    assert sample.shape == (4, 65) and 0 <= sample.min() <= sample.max() < 256
    # the loss is over the slice: ln(256) at seeded weights, not ln(2048)
    assert check["reference_loss"] == pytest.approx(np.log(256), abs=0.8)


def test_every_configuration_in_benchmark_json_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sorted(c["name"] for c in bench["configs"]) == CONFIGS


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    """A four-chip cell costs four times the chip time in every later
    check: at most a quarter of the cells, rounded down, and one always
    may ("one cell takes four chips", as six files said it until PR 52)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    assert {c["chips"] for c in cells} <= {1, 4}
    four = sum(c["chips"] == 4 for c in cells)
    assert 1 <= four <= max(1, len(cells) // 4)


# --------------------------------------------------- reference/decoder.py --

@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_reference_decoder_equals_program_in_float32(kv_heads):
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params, loss_fn

    conf = dict(TINY, num_key_value_heads=kv_heads)
    # the program's flash path (interpreted on the CPU): its
    # attn_impl="reference" does not repeat KV heads without a mesh
    cfg = train.program_config(conf)
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (3, 33), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        program = float(loss_fn(params, {"tokens": tokens}, cfg)[0])
    reference = float(decoder.loss(params, tokens, conf))
    assert abs(program - reference) <= 2e-6 * abs(reference)
    # the tolerance would catch another function of the same weights
    no_rope = float(decoder.loss(params, tokens, dict(conf, rope_theta=1e30)))
    assert abs(no_rope - reference) > 2e-6 * abs(reference)


def test_reference_decoder_blocks_long_queries(monkeypatch):
    """Query blocks change memory only, never the result."""
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params

    cfg = train.program_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65), dtype=np.int32))
    whole = float(decoder.loss(params, tokens, TINY))
    monkeypatch.setattr(decoder, "Q_BLOCK", 16)
    decoder.layer.clear_cache()
    blocked = float(decoder.loss(params, tokens, TINY))
    decoder.layer.clear_cache()
    assert abs(whole - blocked) <= 1e-6 * abs(whole)


# -------------------------------------------------------- trace_reduce.py --

def test_self_times_and_union():
    ev = [("while", 0, 100), ("fusion", 10, 30), ("custom-call.1", 30, 60),
          ("all-reduce.2", 120, 150)]
    assert dict(trace_reduce.self_times(ev)) == {
        "while": 50, "fusion": 20, "custom-call.1": 30, "all-reduce.2": 30}
    assert trace_reduce.union([(0, 100), (10, 30), (120, 150)]) == [
        (0, 100), (120, 150)]


# Event texts as the v5e trace of PR 22 has them (shortened operands).
FLASH = ('%closed_call.11 = (bf16[32,32,512,128]{3,2,1,0:T(8,128)(2,1)}, '
         'f32[32,32,512,128]{3,2,1,0:T(8,128)}) custom-call(bf16[32,32,512,'
         '128]{3,2,1,0} %fusion.406), custom_call_target="tpu_custom_call"')
USES_ONE = ('%fusion.364 = bf16[32,512,4096]{2,1,0:T(8,128)(2,1)} fusion('
            'bf16[4096,4096]{1,0} %custom-call.11), kind=kOutput')
WHILE = ('%while.9 = (s32[]{:T(128)}, bf16[32,512,4096]{2,1,0}) '
         'while((s32[]{:T(128)}) %tuple.1), body=%region_1')
GATHER_START = ('%all-gather-start.1 = (bf16[1024]{0}, bf16[4096]{0}) '
                'all-gather-start(bf16[1024]{0} %p), dimensions={0}')
ALL_REDUCE = ('%all-reduce.26 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} '
              'all-reduce(bf16[2,4096,4096]{2,1,0} %fusion.168), channel_id=57,'
              ' replica_groups=[2,2]<=[4], to_apply=%add.23.clone')
SLICE_START = ('%slice-start.14 = ((bf16[2,4096,16,128]{3,2,1,0}), bf16[1,4096,'
               '16,128]{3,2,1,0}, s32[]{:T(128)}) async-start(bf16[2,4096,16,'
               '128]{3,2,1,0} %gte.1671), calls=%async_computation.14')
GATHER_DONE = ('%all-gather-done.1 = bf16[4096]{0} all-gather-done('
               '(bf16[1024]{0}, bf16[4096]{0}) %all-gather-start.1)')


def test_parse_op():
    assert trace_reduce.parse_op(FLASH) == (
        "closed_call.11", "custom-call", "closed_call.11 custom-call "
        "(bf16[32,32,512,128], f32[32,32,512,128])")
    # an op that READS a custom call's result is no custom call
    assert trace_reduce.parse_op(USES_ONE)[1] == "fusion"
    assert trace_reduce.parse_op(WHILE)[:2] == ("while.9", "while")
    assert trace_reduce.parse_op(GATHER_DONE)[1] == "all-gather-done"
    assert trace_reduce.parse_op(ALL_REDUCE)[1] == "all-reduce"
    # an async slice is data movement on the chip, no collective
    assert not trace_reduce.COLLECTIVE.match(
        trace_reduce.parse_op(SLICE_START)[1])
    assert trace_reduce.parse_op("fusion.3") == ("fusion.3", "fusion",
                                                 "fusion.3")


# The ops' JAX name stacks (the stat ``tf_op``), as ``trace_scopes.op_names``
# reads them from a file; ``ssm_scan`` and ``ssm_chunk`` stand for the scope
# and the kernel a configuration file of a later model would list.
STACKS = {
    FLASH: "jit(step)/while/body/rematted_computation/ssm_scan/ssm_chunk",
    USES_ONE: "jit(step)/transpose(jvp(ffn))/dot_general",
    WHILE: "jit(step)/while/body/dynamic_slice",
    GATHER_START: "jit(step)/while/body/jvp(attn_qkv)/all-gather",
    GATHER_DONE: "jit(step)/while/body/jvp(attn_qkv)/all-gather",
}


def _synthetic_planes():
    ops, mods = [], []
    for i, start in enumerate((0, 1000, 2100)):
        mods.append((f"jit_step({i})", start, start + 900))
        ops += [(WHILE, start, start + 600),
                (FLASH, start + 100, start + 300),
                (GATHER_START, start + 600, start + 610),
                (GATHER_DONE, start + 610, start + 700),
                (USES_ONE, start + 700, start + 900)]
    return {"/device:TPU:0": {"XLA Ops": sorted(ops, key=lambda e: e[1]),
                              "XLA Modules": mods},
            "/host:CPU": {"python": [("make_batch", 890, 950),
                                     ("report", 1900, 2095),
                                     ("session.report", 1850, 2110)]}}


def test_reduce_synthetic_planes():
    """Two steps after a lead-in: window from the lead-in's end.  Without
    the ops' name stacks everything is unscoped and no kernel has a name."""
    out = trace_reduce.reduce_planes(
        _synthetic_planes(), step_module="jit_step",
        annotations=("make_batch", "report"), spans=("session.report",))
    d, = out["devices"]
    assert d["steps"] == 2
    assert d["window_s"] == pytest.approx(2100e-9)
    assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"])
    assert d["idle_s"] == pytest.approx(300e-9)
    assert d["gap_s"] == pytest.approx([100e-9, 200e-9])
    assert d["kernels_s"] == pytest.approx(400e-9)
    assert d["flash_s"] == 0 and d["kernels"] == {
        "unnamed": pytest.approx(200e-9)}
    assert d["scopes"] == {} and d["unscoped_s"] == pytest.approx(900e-9)
    assert d["collective_s"] == pytest.approx(200e-9)
    assert d["collectives_per_step"] == 1
    # the program's span that covers the gap, then the loop's annotation
    assert d["idle_gaps"][0] == ["session.report/report",
                                 pytest.approx(200e-9)]
    assert d["idle_gaps"][1] == ["make_batch", pytest.approx(100e-9)]
    assert all(label.startswith("unscoped/forward ")
               for label, _ in d["device_ops"])
    assert out["host_spans"] == {"make_batch": 1, "report": 1}
    assert trace_reduce.reduce_planes(
        {"/host:CPU": {}}, step_module="jit_step", annotations=()) is None


def test_a_configurations_scopes_and_kernels_reach_the_reduction(monkeypatch):
    """Scopes and kernel names are data: what a configuration file lists
    under ``"scopes"`` and ``"kernels"`` is reduced by, beside
    ``trace_scopes``' own tuples, and the loop hands both over."""
    names = {"/device:TPU:0": STACKS}
    ns = 1e-9
    d, = trace_reduce.reduce_planes(
        _synthetic_planes(), step_module="jit_step", annotations=(),
        names=names, scopes=["ssm_scan"], kernels=["ssm_chunk"])["devices"]
    assert d["scopes"] == {
        "ssm_scan": {"remat": pytest.approx(200 * ns)},
        "scan": {"forward": pytest.approx(400 * ns)},
        "attn_qkv": {"forward": pytest.approx(100 * ns)},
        "ffn": {"backward": pytest.approx(200 * ns)}}
    assert d["unscoped_s"] == 0
    assert d["kernels"] == {"ssm_chunk.remat": pytest.approx(200 * ns)}
    assert d["kernels_s"] == pytest.approx(400 * ns) and d["flash_s"] == 0
    assert d["device_ops"][0] == [
        "scan/forward while.9 while (s32[], bf16[32,512,4096])",
        pytest.approx(800 * ns)]
    assert {label.split(" ")[0] for label, _ in d["device_ops"]} == {
        "scan/forward", "ssm_scan/remat", "attn_qkv/forward", "ffn/backward"}
    # without the configuration's names the same ops fall to the scan
    d, = trace_reduce.reduce_planes(
        _synthetic_planes(), step_module="jit_step", annotations=(),
        names=names)["devices"]
    assert "ssm_scan" not in d["scopes"]
    assert d["scopes"]["scan"] == {"forward": pytest.approx(400 * ns),
                                   "remat": pytest.approx(200 * ns)}
    assert d["kernels"] == {"unnamed.remat": pytest.approx(200 * ns)}

    # the loop: what it hands to the reduction of a traced run
    import jax.numpy as jnp

    from benchmark.loops import train

    seen = {}

    def reduce_file(path, **kw):
        seen.update(kw, path=path, there=os.path.isfile(path))
        return {"devices": []}

    monkeypatch.setattr(trace_reduce, "reduce_file", reduce_file)
    config = {"conf": {"scopes": ["ssm_scan"], "kernels": ["ssm_chunk"]},
              "job": {"traced_steps": 1}, "trace_dir": None}
    assert train._traced_steps(
        config, lambda n: jnp.ones(n).block_until_ready(),
        "jit_step") == {"devices": []}
    assert seen["there"] and not os.path.exists(seen["path"])
    assert (seen["scopes"], seen["kernels"]) == (["ssm_scan"], ["ssm_chunk"])
    assert seen["spans"] == train.PROGRAM_SPANS
    assert seen["annotations"] == train.ANNOTATIONS


RECORDED = os.path.join(BENCH, "testdata", "mistral7b-train-s512.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The trace recorded on the v5e (benchmark/testdata), reduced as the
    loop reduces it, and what ``expected.json`` says of it."""
    import gzip
    import shutil

    from benchmark.loops import train

    with open(os.path.join(BENCH, "testdata", "expected.json")) as f:
        expected = json.load(f)
    path = str(tmp_path_factory.mktemp("trace") / "recorded.xplane.pb")
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = trace_reduce.reduce_file(
        path, step_module=expected["step_module"],
        annotations=train.ANNOTATIONS, spans=train.PROGRAM_SPANS)
    return out, expected


def test_reduce_recorded_chip_trace(recorded):
    """Busy + idle = window, the step found, the kernels found by name,
    every op under a scope and a phase."""
    from benchmark.loops import train

    out, expected = recorded
    d, = out["devices"]
    assert d["steps"] == expected["steps"] == len(d["step_s"])
    assert d["busy_s"] + d["idle_s"] == pytest.approx(d["window_s"],
                                                      rel=1e-12)
    assert d["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    idle_pct = 100.0 * d["idle_s"] / d["window_s"]
    assert idle_pct == pytest.approx(expected["idle_pct"], rel=1e-6)
    assert idle_pct + 100.0 * d["busy_s"] / d["window_s"] == \
        pytest.approx(100.0, rel=1e-12)
    # the Mosaic kernels: forward, dKV, dQ per layer and step, each under
    # its name, and nothing that merely reads a custom call's result
    assert d["flash_s"] == pytest.approx(expected["flash_s"], rel=1e-9)
    assert d["kernels_s"] == d["flash_s"]  # a dense step has no other
    assert sorted(d["kernels"]) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert sum(d["kernels"].values()) * d["steps"] == pytest.approx(
        d["flash_s"], rel=1e-9)
    assert 0.02 < d["flash_s"] / sum(d["step_s"]) < 0.04
    assert statistics.median(d["step_s"]) * 1e3 == pytest.approx(
        expected["step_ms_median"], rel=1e-9)
    assert d["collective_s"] == 0 and d["collectives_per_step"] == 0
    # scopes + scan + unscoped = busy, to the nanosecond
    scoped = sum(t for row in d["scopes"].values() for t in row.values())
    assert (scoped + d["unscoped_s"]) * d["steps"] == pytest.approx(
        d["busy_s"], rel=1e-9)
    assert set(d["scopes"]) == set(trace_scopes.SCOPES) - set(
        trace_scopes.MOE_SCOPES) | {trace_scopes.SCAN}
    assert len(d["device_ops"]) == 10 and 0 < len(d["idle_gaps"]) <= 5
    assert d["device_ops"][0][0] == expected["top_op"]
    assert all(len(label) <= 120 for label, _ in d["device_ops"])
    places = {f"{s}/{p}" for s in d["scopes"] for p in trace_scopes.PHASES}
    assert {label.split(" ")[0] for label, _ in d["device_ops"]} <= places
    labels = {"/".join(filter(None, (s, a)))
              for s in ("",) + train.PROGRAM_SPANS
              for a in ("",) + train.ANNOTATIONS} - {""}
    assert {label for label, _ in d["idle_gaps"]} <= labels | {"unannotated"}
    assert all(out["host_spans"][n] == expected["steps"] + 1
               for n in train.ANNOTATIONS)


with open(os.path.join(BENCH, "testdata", "expected.json")) as _f:
    RECORDED_READERS = sorted(json.load(_f)["readers"].items())


@pytest.mark.parametrize("metric,value", RECORDED_READERS,
                         ids=[m for m, _ in RECORDED_READERS])
def test_readers_on_the_recorded_chip_trace(recorded, metric, value):
    """Every reader of the trace on the recorded run, against the values
    ``expected.json`` holds (each checked against ``python -m
    ray_tpu.scripts step-breakdown`` of the same file when it was
    recorded); None where the run has nothing for it."""
    out, expected = recorded
    with open(os.path.join(BENCH, "jobs", expected["job"] + ".json")) as f:
        job = json.load(f)
    run = {"worker": {"trace": out, "window": {}},
           "conf": _conf(expected["config"]), "job": job, "chips": 1,
           "peak": PEAK}
    got = _reader(metric).read(run)
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value, rel=1e-9)
    if metric.endswith("_pct") or metric.endswith("_roofline"):
        assert got is None or 0 <= got < 100


def test_step_shares_of_the_recorded_trace_make_a_hundred():
    with open(os.path.join(BENCH, "testdata", "expected.json")) as f:
        readers = json.load(f)["readers"]
    parts = [v for m, v in readers.items() if m.startswith("step.")
             and m != "step.remat_pct"]
    assert len(parts) == 7
    assert sum(parts) == pytest.approx(100.0, abs=0.01)


# ------------------------------------------------------------ the loop --

def _rehearsal_loop(config):
    """Test-only entry: the train loop without the chip requirement, with
    the marks ``loop`` sets round the bring-up it makes itself."""
    import time

    marks = {"loop_start": time.time()}
    import jax

    from benchmark.loops import train
    from ray_tpu.air import session

    marks["import_jax"] = time.time()
    devs = jax.devices()
    marks["devices"] = time.time()
    session.report(train.measure(config, devs, marks))


BF16 = {"param_dtype": {"value": "bfloat16"}, "dtype": {"value": "bfloat16"}}


@pytest.mark.parametrize("conf,mesh,check_rows", [
    (dict(TINY, assumed=BF16), None, 4),
    # 8 virtual devices: dp=2 x fsdp=2 split the rows four ways
    (dict(TINY, assumed=BF16), {"fsdp": 2, "tp": 2}, 4),
    (TINY_MOE, None, 2),
], ids=["dense-one-device", "dense-fsdp2-tp2", "moe-one-device"])
def test_train_loop_rehearsal_on_cpu_worker(conf, mesh, check_rows):
    """The ONE loop at a tiny size through JaxTrainer.fit() with a CPU
    worker: a dense cell, the mesh cell and the MoE cell.  Asserts the
    shape of what comes back, no speed."""
    import ray_tpu as ray
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    from benchmark.loops import train

    moe = "num_experts" in conf
    job = {"loop": "train", "rows": 4, "seq": 64, "mesh": mesh,
           "check_rows": check_rows, "warmup_steps": 2, "traced_steps": 2}
    ray.init(num_cpus=4, num_tpus=0)
    try:
        result = JaxTrainer(
            _rehearsal_loop,
            train_loop_config={"conf": conf, "job": job, "chips": 0,
                               "peaks": {}, "seed": 2147483653,
                               "seconds": 1.0, "trace": True,
                               "trace_dir": None},
            scaling_config=ScalingConfig(num_workers=1,
                                         tpu_chips_per_worker=0)).fit()
    finally:
        ray.shutdown()
    assert result.error is None, result.error
    w = result.metrics
    assert w["device"]["platform"] == "cpu"
    win = w["window"]
    assert win["attempted"] == win["steps"] >= 1 and win["failed"] == 0
    assert win["tokens"] == win["steps"] * 4 * 64
    assert win["compiles"] == 0 and win["error"] is None
    assert w["trace"] is None  # a CPU trace has no device plane to read
    # every step reported, as a user's loop does: warm-up, window, traced
    assert len(result.metrics_history) == 2 + win["steps"] + 3 + 1
    # bfloat16 against the float32 reference at a tiny size, on norm
    # weights drawn from the seed: the total loss and each of its parts
    check = w["check"]
    assert abs(check["program_loss"] - check["reference_loss"]) \
        < 2e-2 * check["reference_loss"]
    assert check["rtol"] == pytest.approx(1e-4 * (4096 / (check_rows * 64))
                                          ** 0.5)
    parts = ("loss", "aux_loss", "z_loss") if moe else ("loss",)
    assert set(check["reference_parts"]) == set(parts) | {"total"}
    for part in parts:
        assert check["program_parts"][part] == pytest.approx(
            check["reference_parts"][part], rel=3e-2), part
    if moe:
        assert check["step_metrics"] == {"moe_dropped": 0.0}
        assert win["step_metrics"]["moe_dropped"] == 0
        assert 1.0 <= win["step_metrics"]["moe_load_max_over_mean"] <= 8.0
        assert _reader("moe.load_max_over_mean").read({"worker": w}) == \
            win["step_metrics"]["moe_load_max_over_mean"]
    else:
        assert check["step_metrics"] == win["step_metrics"] == {}
        assert _reader("moe.load_max_over_mean").read({"worker": w}) is None
    run = {"worker": w, "process_start": w["loop_start"] - 1.0}
    assert train.end_to_end(run)["train_tokens_per_s"] > 0
    # a CPU worker is granted no chip and opens no ``jax.backend_init``:
    # the runtime's start is the loop's own marks, and the identity holds
    assert "jax.backend_init" not in w["_spans"]
    start = train.runtime_start_s(run)
    assert start == w["setup_marks"]["devices"] - w["setup_marks"]["import_jax"]
    assert train.end_to_end(run)["setup_s"] + start == pytest.approx(
        w["window_start"] - run["process_start"])
    assert train.end_to_end(run)["setup_s"] > 1.0

    # correct(): a chip reports its memory, and bfloat16 at this size is
    # outside the chip check's tolerance, so both are put right here
    good = dict(w, peak_bytes_in_use=[1] * (4 if mesh else 1), check=dict(
        check, program_loss=check["reference_loss"], token_nll_rms=0.0))
    rows = train.compared({"worker": good})
    assert [r["what"] for r in rows][:5] == [
        "per-token loss apart from the reference's, RMS in nats",
        "loss apart from the reference's, relative", "failed steps",
        "compiles in the window", "memory peak, fullest chip over emptiest"]
    assert all(r["ok"] for r in rows) and len(rows) == (6 if moe else 5)
    assert train.correct({"worker": good}) is True
    for broken in (
            dict(check=dict(good["check"], token_nll_rms=1.001
                            * check["token_nll_limit"])),
            dict(check=dict(good["check"], token_nll_rms=float("nan"))),
            dict(check=dict(check, program_loss=1.001
                            * check["reference_loss"])),
            dict(check=dict(check, program_loss=float("nan"))),
            dict(window=dict(win, failed=1)),
            dict(window=dict(win, compiles=1)),
            dict(window=dict(win, steps=0)),
            dict(window=dict(win, error="RuntimeError()")),
            dict(peak_bytes_in_use=[0]), dict(peak_bytes_in_use=[10, 13])):
        assert train.correct({"worker": dict(good, **broken)}) is False, broken
    if moe:
        assert train.correct({"worker": dict(good, window=dict(
            win, step_metrics=dict(win["step_metrics"], moe_dropped=1.0)))
        }) is False


def test_a_missing_norm_shows_in_a_dense_model():
    """Why the loop draws the norm weights of the parameters it checks: at
    step 0 they are all 1 and what they norm has unit RMS, so a program
    that left a norm out would read the same loss.  With the weights drawn,
    the reference with one norm's weights back at 1 (the nearest thing to
    the norm left out) is far outside the tolerance."""
    import jax
    import numpy as np

    from benchmark.loops import train
    from benchmark.reference import decoder
    from ray_tpu.models.llama import init_params, loss_fn

    cfg = train.program_config(TINY)
    params = init_params(jax.random.PRNGKey(1), cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (4, 65), dtype=np.int32))
    rng = np.random.default_rng(2)
    drawn = jax.tree_util.tree_map_with_path(
        lambda path, a: a * rng.uniform(0.5, 1.5, a.shape).astype(np.float32)
        if str(path[-1].key).endswith("norm") else a, params)
    with jax.default_matmul_precision("highest"):
        program = float(loss_fn(drawn, {"tokens": tokens}, cfg)[0])
    parts = decoder.loss_parts(drawn, tokens, TINY)
    reference = float(parts["total"])
    assert float(parts["loss"]) == reference
    assert abs(program - reference) <= 2e-6 * reference
    rtol = decoder.loss_rtol(4 * 64)
    for name in ("attn_norm", "mlp_norm"):
        without = dict(drawn, layers=dict(
            drawn["layers"], **{name: params["layers"][name]}))
        apart = abs(float(decoder.loss(without, tokens, TINY)) - reference)
        assert apart > 3 * rtol * reference, (name, apart / reference)


@pytest.mark.parametrize("conf,seq", [(TINY, 64), (TINY_MOE, 64)],
                         ids=["dense", "moe"])
def test_control_readings_at_a_size_a_test_can_hold(conf, seq):
    """``benchmark/control.py`` at CPU size, float32: the program's
    per-token losses are the reference's to rounding (``sound``), and the
    reference with its matrices through int8 (THE control), float8, or its
    log-probabilities kept in bfloat16 stands far off.  What they read at
    a cell's own size is a chip reading: PERF.md section 6, PR 29."""
    import jax

    from benchmark import control

    conf = dict(conf, assumed={"param_dtype": {"value": "float32"},
                               "dtype": {"value": "float32"}})
    job = {"rows": 4, "seq": seq, "mesh": None, "check_rows": 4}
    for seed in (2147483653, 7, 11):
        got = control.readings(conf, job, seed, jax.devices())
        assert got["limit"] == conf["check"]["token_nll_rms"]
        assert got["sound"] < 1e-4 and got["mean_rel"] < 1e-5
        for name in ("int8", "fp8", "bf16_logp"):
            assert got[name] > 100 * got["sound"], (name, got)
        assert got["int8"] < got["fp8"]


# -------------------------------------------------------------- run.py --

def test_run_exits_non_zero_without_a_chip():
    """This machine has no chip: no result line, a reason on stderr."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip().startswith("{")


def test_run_holds_no_cell_configuration_or_metric_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "run.py")) as f:
        source = f.read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w["traffic"] for w in bench["workloads"]]
    assert [n for n in names if n in source] == []
    # every name in BENCHMARK.json has its file
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "jobs",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
