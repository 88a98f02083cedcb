"""``setup_s`` since PR 62: the PROGRAM's set-up, process start to the first
measured step LESS the TPU runtime's start (``loops/train.py::
runtime_start_s``: the program's span ``jax.backend_init``, else the loop's
own marks round ``jax.devices()``, else the run fails).  The per-layer
readers keep reading the WALL; the span that is subtracted holds one call.
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_setup_reading.py
-q``; its cases count in tier-1 through ``tier1_cases.py``."""

import ast
import json
import os

import pytest

from benchmark.loops import train
from benchmark.tests.test_setup_readers import (
    ROOT, SPANS, _module, _run, _span)

BRING_UP = ("device.bring_up", "jax.import", "jax.backend_init")


def _whole(spans, marks=None, process_start=1000.0, window_start=1040.0,
           chips=1):
    """A run as ``run.py`` hands it to ``end_to_end``: ``_run`` of the span
    fixtures with the window's counts and the loop's marks."""
    run = _run(spans, process_start, window_start)
    run["chips"] = chips
    run["worker"].update(
        window={"tokens": 10 * 8192, "elapsed_s": 10.25},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    if marks is not None:
        run["worker"]["setup_marks"] = marks
    return run


# The program opened the chips before the loop: the loop's own two marks
# lie microseconds apart (details of a chip run at PR 61).
LATE_MARKS = {"loop_start": 1013.5, "import_jax": 1013.500006,
              "devices": 1013.500014}
# A program that leaves the bring-up to the loop (before PR 50): no span
# of the three, ``import jax`` 1003-1005.5 and ``jax.devices()`` to 1013.5.
LOOP_MARKS = {"loop_start": 1003.0, "import_jax": 1005.5, "devices": 1013.5}
NO_BRING_UP = {k: v for k, v in SPANS.items() if k not in BRING_UP}
# Four chips: ONE process starts four chips' runtime under the one span.
FOUR = dict(SPANS, **{
    "device.bring_up": _span((1003.0, 1020.25)),
    "jax.import": _span((1003.0, 1005.5)),
    "jax.backend_init": _span((1005.5, 1020.25))})

RUNS = {
    # with the span the marks time a call that returns at once: they are
    # neither added to it nor taken for it
    "the-span": (_whole(SPANS, LATE_MARKS), 8.0),
    "the-span-alone": (_whole(SPANS), 8.0),
    "the-loops-marks": (_whole(NO_BRING_UP, LOOP_MARKS), 8.0),
    "four-chips": (_whole(FOUR, LATE_MARKS, chips=4), 14.75),
    "another-start": (_whole(SPANS, LATE_MARKS, 999.123456, 1037.654321),
                      8.0),
}


@pytest.mark.parametrize("name", RUNS)
def test_set_up_is_the_wall_less_the_runtimes_start(name):
    run, start = RUNS[name]
    wall = run["worker"]["window_start"] - run["process_start"]
    assert train.runtime_start_s(run) == pytest.approx(start, abs=1e-9)
    got = train.end_to_end(run)
    assert got["setup_s"] == pytest.approx(wall - start, abs=1e-9)
    # the identity a run satisfies
    assert got["setup_s"] + train.runtime_start_s(run) == pytest.approx(
        wall, abs=1e-9)
    # the other metric is what it was
    assert got["train_tokens_per_s"] == 10 * 8192 / 10.25
    assert set(got) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("spans,marks", [
    (NO_BRING_UP, None), (NO_BRING_UP, {"loop_start": 1003.0}),
    (None, None), ({}, {"import_jax": 1005.5})],
    ids=["no-marks", "half-the-marks", "a-failed-session", "one-mark"])
def test_neither_the_span_nor_the_marks_fails_by_message(spans, marks):
    run = _whole(spans, marks)
    with pytest.raises(RuntimeError, match="neither the span "
                       "jax.backend_init nor the loop's marks"):
        train.end_to_end(run)
    with pytest.raises(RuntimeError, match="setup_s"):
        train.runtime_start_s(run)


@pytest.mark.parametrize("span", [
    _span((1005.5, 1013.5), (1014.0, 1020.0)),      # opened twice
    _span((1005.5, 1013.5), count=2),               # ... and said so
    _span((998.0, 1006.0)),                         # began before the start
    _span((1036.0, 1044.0)),                        # ends in the window
    _span((1050.0, 1058.0))],                       # all in the window
    ids=["twice", "count-two", "early", "straddles", "late"])
def test_a_span_that_is_not_one_opening_inside_set_up_fails(span):
    run = _whole(dict(SPANS, **{"jax.backend_init": span}), LATE_MARKS)
    with pytest.raises(RuntimeError, match="the reading takes ONE"):
        train.end_to_end(run)


@pytest.mark.parametrize("name", ["the-span", "four-chips", "another-start"])
def test_the_books_close_on_the_wall_not_on_the_new_reading(name):
    """``setup.unattributed_s`` + the union = the WALL of the run; the
    bring-up's readers read what they read."""
    run, start = RUNS[name]
    mod = _module("setup.unattributed_s")
    wall = run["worker"]["window_start"] - run["process_start"]
    union, total = mod.covered(run)
    assert total == wall
    assert union + mod.read(run) == pytest.approx(wall, abs=1e-9)
    setup_s = train.end_to_end(run)["setup_s"]
    assert union + mod.read(run) == pytest.approx(setup_s + start, abs=1e-9)
    assert union + mod.read(run) > setup_s + 1.0
    spans = run["worker"]["_spans"]
    assert _module("setup.device_bringup_s").read(run) == \
        spans["device.bring_up"]["total_s"] == pytest.approx(start + 2.5)
    assert _module("setup.jax_import_s").read(run) == 2.5


def _calls_named(tree, span):
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call) and n.args
            and isinstance(n.args[0], ast.Constant)
            and n.args[0].value == span]


def test_the_subtracted_span_holds_one_call():
    """The program opens the span the benchmark subtracts, so nothing else
    may run under it: in all of ``ray_tpu`` ONE call names it, the ``with``
    of ``train/backend.py::bring_up``, and its body is the one statement
    that starts the runtime.  A PR that wants otherwise is a ``benchmark``
    PR."""
    package = os.path.join(ROOT, "ray_tpu")
    found = {}      # file -> its tree, where some call names the span
    for folder, _, files in os.walk(package):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py"):
                with open(path) as f:
                    source = f.read()
                # most files do not hold the string: parse those that do
                if "jax.backend_init" in source:
                    tree = ast.parse(source)
                    if _calls_named(tree, "jax.backend_init"):
                        found[os.path.relpath(path, package)] = tree
    (where, tree), = found.items()
    assert where == os.path.join("train", "backend.py")
    assert len(_calls_named(tree, "jax.backend_init")) == 1
    bring_up, = [n for n in tree.body if isinstance(n, ast.FunctionDef)
                 and n.name == "bring_up"]
    withs = [n for n in ast.walk(bring_up) if isinstance(n, ast.With)
             and any(_calls_named(item.context_expr, "jax.backend_init")
                     for item in n.items)]
    held, = withs
    assert len(held.items) == 1
    assert ast.unparse(held.items[0].context_expr) == \
        "tracing.span('jax.backend_init')"
    assert [ast.unparse(stmt) for stmt in held.body] == [
        "devices = jax.local_devices()"]


def test_the_entry_keeps_its_name_and_a_bound_no_wider():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    bound = entry.pop("bound")
    assert entry == {"name": "setup_s", "unit": "s", "better": "lower",
                     "source": "host_clock"}
    assert 0.01 <= bound <= 0.1
    rate, = [m for m in bench["end_to_end"] if m["name"] != "setup_s"]
    assert rate == {"name": "train_tokens_per_s", "unit": "tokens/s",
                    "better": "higher", "bound": 0.025,
                    "source": "host_clock"}
