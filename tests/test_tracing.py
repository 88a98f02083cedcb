"""Tracing/timeline (reference: `ray timeline` scripts.py:1840 + task
events; handler latency stats per src/ray/common/event_stats.h)."""
import json
import time

import pytest

import ray_tpu as ray
from ray_tpu.util.tracing import chrome_trace, get_task_spans, handler_stats


@pytest.fixture
def init2():
    ray.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray.shutdown()


def test_timeline_captures_task_and_actor_spans(init2, tmp_path):
    @ray.remote
    def work(i):
        time.sleep(0.002)
        return i

    @ray.remote
    class A:
        def m(self):
            time.sleep(0.002)
            return 1

    a = A.remote()
    ray.get([work.remote(i) for i in range(40)])
    ray.get([a.m.remote() for _ in range(10)])
    # Spans flush on worker queue drain; give the periodic flusher a beat.
    deadline = time.time() + 5
    while time.time() < deadline:
        spans = get_task_spans()
        names = [s["name"] for s in spans]
        if names.count("work") >= 40 and names.count("actor.m") >= 10:
            break
        time.sleep(0.3)
    assert names.count("work") >= 40, names[:5]
    assert names.count("actor.m") >= 10
    for s in spans:
        assert s["end"] >= s["start"]
        assert s["worker_id"]

    out = ray.timeline(str(tmp_path / "trace.json"))
    events = json.load(open(out))
    xs = [e for e in events if e.get("ph") == "X"]
    assert len(xs) >= 50
    assert all({"name", "ts", "dur", "pid", "tid"} <= set(e) for e in xs)
    # Perfetto lane metadata present.
    assert any(e.get("ph") == "M" for e in events)


def test_handler_stats_expose_head_latency(init2):
    @ray.remote
    def f():
        return None

    ray.get([f.remote() for _ in range(20)])
    stats = handler_stats()
    tags = {s["handler"] for s in stats}
    assert tags, stats
    for s in stats:
        assert s["count"] > 0 and s["mean_us"] >= 0


def test_spans_visible_from_worker(init2):
    @ray.remote
    def f():
        return None

    @ray.remote
    def probe():
        from ray_tpu.util.tracing import get_task_spans
        return len(get_task_spans())

    ray.get([f.remote() for _ in range(10)])
    time.sleep(0.6)
    assert ray.get(probe.remote()) >= 1


# ------------------------------------------------ the span primitive --

def _wait_spans(want, timeout=8.0):
    """Spans flush with the workers' 0.25 s flusher: poll until every
    name in ``want`` is at the head."""
    deadline = time.time() + timeout
    while True:
        spans = get_task_spans()
        names = {s["name"] for s in spans}
        if set(want) <= names or time.time() > deadline:
            assert set(want) <= names, sorted(names)
            return spans
        time.sleep(0.1)


def test_span_parent_is_enclosing_or_submitting_span(init2):
    from ray_tpu.util import tracing

    @ray.remote
    def leaf():
        return 1

    @ray.remote
    def outer():
        from ray_tpu.util import tracing as t
        with t.span("inner.work", rows=3):
            time.sleep(0.002)
        return ray.get(leaf.remote())  # a task submitted by a task

    @ray.remote
    class A:
        def m(self):
            return 1

    a = A.remote()
    with tracing.span("driver.batch") as batch:
        assert ray.get(outer.remote()) == 1
        assert ray.get(a.m.remote()) == 1
    spans = _wait_spans(
        ["driver.batch", "outer", "inner.work", "leaf", "actor.m"])
    by_name = {s["name"]: s for s in spans}
    outer_s, inner = by_name["outer"], by_name["inner.work"]
    # Enclosing span in the same thread; the submitter's span across
    # processes, for a task from the driver and for one from a task.
    assert inner["parent"] == outer_s["span_id"]
    assert inner["task_id"] == outer_s["task_id"]
    assert inner["args"] == {"rows": 3}
    assert outer_s["parent"] == batch.id
    assert by_name["actor.m"]["parent"] == batch.id
    assert by_name["leaf"]["parent"] == outer_s["span_id"]
    assert by_name["driver.batch"]["worker_id"] == "driver"
    assert by_name["driver.batch"]["parent"] is None
    ids = [s["span_id"] for s in spans]
    assert len(set(ids)) == len(ids)
    for name in ("outer", "leaf", "actor.m"):
        s = by_name[name]
        assert s["submitted"] <= s["start"] <= s["end"], s
    assert "submitted" not in inner
    # The driver has a lane of its own in the timeline.
    lanes = [e["args"]["name"] for e in chrome_trace(spans)
             if e.get("name") == "thread_name"]
    assert "driver" in lanes


def test_span_never_imports_jax():
    """The benchmark's driver must stay off the chip: a span in a
    process without JAX leaves it without JAX."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.span('outer'):\n"
            "    with tracing.span('inner') as s:\n"
            "        pass\n"
            "assert s.parent is not None and s.start > 0\n"
            "assert 'jax' not in sys.modules, 'span() imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_span_is_on_the_profilers_clock(tmp_path):
    """With JAX in the process a span is a TraceAnnotation: an event of
    the /host:CPU plane.  Clock rule: ``start_ns`` counts from the stat
    ``profile_start_time`` of the plane ``Task Environment``, which is
    ``time.time()`` in ns (CLOCK_REALTIME)."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from ray_tpu.util import tracing

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        recorded = []
        for _ in range(3):
            with tracing.span("probe.span") as s:
                jnp.ones((8, 8)).sum().block_until_ready()
                time.sleep(0.005)
            recorded.append((s.start, time.time()))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    t0 = dict(planes["Task Environment"].stats)["profile_start_time"]
    events = sorted((e.start_ns, e.duration_ns)
                    for line in planes["/host:CPU"].lines
                    for e in line.events if e.name == "probe.span")
    assert len(events) == 3
    for (start_ns, dur_ns), (start, end) in zip(events, recorded):
        assert abs(t0 + start_ns - start * 1e9) < 2e6, (t0, start_ns, start)
        assert 5e6 <= dur_ns <= (end - start) * 1e9 + 2e6


def test_fit_returns_its_spans():
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    def loop(config):
        for i in range(config["reports"]):
            session.report({"i": i})

    ray.init(num_cpus=2, ignore_reinit_error=True)
    try:
        result = JaxTrainer(
            loop, train_loop_config={"reports": 3},
            scaling_config=ScalingConfig(num_workers=1)).fit()
    finally:
        ray.shutdown()
    assert result.error is None
    spans = result.metrics["_spans"]
    # (a test before this one may have made this process watch itself:
    # a pause or a lag during fit() is then a span of the driver's too;
    # the worker's periodic thread is one where it took over a millisecond)
    assert set(spans) - {"gc.pause", "host.lag", "worker.flush"} == {
        "train.fit", "train.placement_group", "train.start_workers",
        "train.backend_start", "train.run", "train.shutdown",
        "sched.wait", "worker.spawn", "train.session_start", "train.loop",
        "session.report"}
    assert spans["session.report"]["count"] == 3 == len(
        result.metrics_history)
    assert result.metrics["i"] == 2
    assert "_spans" not in result.metrics_history[-1]
    for name, s in spans.items():
        assert set(s) - {"clock"} == {"count", "total_s", "max_s",
                                      "first_start", "last_end",
                                      "recent"}, name
        # the one clocked name: a thread_clock() beside each (start, end)
        assert ("clock" in s) == (name == "session.report"), name
        assert 0 <= s["max_s"] <= s["total_s"], name
        assert s["first_start"] <= s["last_end"], name
    fit = spans["train.fit"]
    for name, s in spans.items():  # one clock, one machine
        assert fit["first_start"] <= s["first_start"], name
        assert s["last_end"] <= fit["last_end"], name
    inside = spans["train.start_workers"]
    assert inside["first_start"] <= spans["sched.wait"]["first_start"]
    assert spans["worker.spawn"]["last_end"] <= inside["last_end"]
    assert spans["train.loop"]["total_s"] <= spans["train.run"]["total_s"]
    import pickle
    # 13 (start, end) pairs and three reports' clocks in it
    assert len(pickle.dumps(spans)) < 2048


# ------------------- spans after the fact, JAX's pipeline, the collector --

def test_record_reaches_collectors_and_head_with_its_cause(init2):
    from ray_tpu.util import tracing

    with tracing.collect() as got:
        with tracing.span("probe.outer") as outer:
            tracing.record("probe.past", 100.0, 100.5, what="x")
        tracing.record("probe.past", 101.0, 101.25)
    past = got.summary["probe.past"]
    assert past["count"] == 2 and past["total_s"] == 0.75
    assert past["max_s"] == 0.5
    assert past["recent"] == [(100.0, 100.5), (101.0, 101.25)]
    first, second = [s for s in get_task_spans()
                     if s["name"] == "probe.past"]
    assert (first["start"], first["end"]) == (100.0, 100.5)
    assert first["parent"] == outer.id and first["args"] == {"what": "x"}
    assert first["worker_id"] == "driver" and first["kind"] == "span"
    assert second["parent"] is None and "args" not in second
    assert len({first["span_id"], second["span_id"], outer.id}) == 3


def test_jax_pipeline_is_spans_by_function(init2):
    """A nested ``jit`` compiled under ``collect()``: one ``jax.trace``,
    ``jax.lower``, ``jax.compile`` for the outer function — the inner
    ``jit``'s trace, which JAX reports too, is inside the outer's and is
    not counted again; watching twice records once."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from ray_tpu.util import tracing

    tracing.watch_process()
    tracing.watch_process()

    @jax.jit
    def probe_inner(x):
        return jnp.sin(x) * 2

    def probe_step(x):
        return probe_inner(x) + probe_inner(x * 2)

    traced = []

    def on_span(event, start, end, fun_name="", **_):
        if event.endswith("/jaxpr_trace_duration"):
            traced.append((fun_name, start, end))

    x = jnp.ones((8, 8))  # its own eager programs: before collecting
    monitoring.register_event_time_span_listener(on_span)
    try:
        with tracing.collect() as got:
            with tracing.span("probe.compiling") as outer:
                jax.jit(probe_step).lower(x).compile()
    finally:
        monitoring.unregister_event_time_span_listener(on_span)
    # JAX reported the inner jit's trace (and jnp's own jits'), each
    # inside the outer function's, which ends last.
    assert "probe_inner" in [t[0] for t in traced[:-1]]
    fun, start, end = traced[-1]
    assert fun == "probe_step"
    assert all(start <= a <= b <= end for _, a, b in traced)
    summary = got.summary
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert summary[name]["count"] == 1, name
    assert summary["jax.trace"]["recent"] == [(start, end)]
    assert summary["jax.trace"]["total_s"] == end - start
    assert "jax.cache_load" not in summary  # no persistent cache here
    mine = {s["name"]: s for s in get_task_spans()
            if s["parent"] == outer.id}
    assert mine["jax.trace"]["args"] == {"fun": "probe_step"}
    assert mine["jax.lower"]["args"] == {"fun": "jit(probe_step)"}
    assert mine["jax.compile"]["args"] == {"fun": "jit(probe_step)"}
    assert set(mine) == {"jax.trace", "jax.lower", "jax.compile"}

    # A helper that a lowering rule traces is lowering's time: JAX's own
    # events, as ``log_elapsed_time`` sends them, with nothing compiled.
    trace, lower = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
    with tracing.collect() as got:
        monitoring.record_scalar(lower, 10.0, fun_name="jit(f)")
        monitoring.record_scalar(trace, 11.0, fun_name="add")
        monitoring.record_event_time_span(trace, 11.0, 12.0, fun_name="add")
        monitoring.record_event_time_span(lower, 10.0, 14.0,
                                          fun_name="jit(f)")
        # ... and one whose start nobody saw is recorded as it is
        monitoring.record_event_time_span(trace, 20.0, 21.0, fun_name="g")
    assert got.summary["jax.lower"]["recent"] == [(10.0, 14.0)]
    assert got.summary["jax.trace"]["recent"] == [(20.0, 21.0)]
    assert tracing.current_span() is None


def test_jax_cache_load_and_miss_are_children_of_the_compile(init2,
                                                             tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.util import tracing

    tracing.watch_process()
    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in settings}

    def probe_cached(x):
        return jnp.cos(x) + 3

    x = jnp.ones((4, 4))
    try:
        for k, v in settings.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        with tracing.collect() as got:
            jax.jit(probe_cached).lower(x).compile()  # a miss, written
            jax.clear_caches()
            jax.jit(probe_cached).lower(x).compile()  # read back
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    summary = got.summary
    assert summary["jax.compile"]["count"] == 2
    assert summary["jax.cache_miss"]["count"] == 1
    assert summary["jax.cache_miss"]["total_s"] == 0.0
    assert summary["jax.cache_load"]["count"] == 1
    spans = [s for s in get_task_spans()
             if (s.get("args") or {}).get("fun") == "jit(probe_cached)"]
    compiles = [s for s in spans if s["name"] == "jax.compile"]
    miss, = [s for s in spans if s["name"] == "jax.cache_miss"]
    load, = [s for s in spans if s["name"] == "jax.cache_load"]
    assert miss["parent"] == compiles[0]["span_id"]
    assert load["parent"] == compiles[1]["span_id"]
    assert compiles[1]["start"] <= load["start"] <= load["end"] \
        <= compiles[1]["end"]


def test_recent_is_bounded_ordered_and_survives_merge():
    from ray_tpu.util import tracing

    got = tracing._Collected()
    for i in range(300):
        got.add("probe.many", float(i), i + 0.5)
    many = got.summary["probe.many"]
    assert many["count"] == 300 and tracing.RECENT == 256
    assert many["recent"] == [(float(i), i + 0.5) for i in range(44, 300)]
    assert isinstance(many["recent"], list)

    into = tracing._Collected()
    into.add("probe.many", 500.0, 501.0)
    into.add("probe.other", 1.0, 2.0)
    into.merge(got.summary)
    into.merge({"probe.old": {  # a summary from before ``recent``
        "count": 2, "total_s": 3.0, "max_s": 2.0, "first_start": 0.0,
        "last_end": 9.0}})
    merged = into.summary
    assert merged["probe.many"]["count"] == 301
    assert merged["probe.many"]["total_s"] == 151.0
    recent = merged["probe.many"]["recent"]
    assert len(recent) == 256 and recent == sorted(recent)
    assert recent[0] == (45.0, 45.5) and recent[-1] == (500.0, 501.0)
    assert merged["probe.other"]["recent"] == [(1.0, 2.0)]
    assert merged["probe.old"]["recent"] == []
    assert merged["probe.old"]["count"] == 2


def test_every_report_carries_the_hosts_clock():
    """``_timestamp`` and ``_time_this_iter_s`` in every report, and the
    same clock in ``_spans["session.report"]["recent"]``, which is what a
    reader of the LAST report has of the earlier ones."""
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    def loop(config):
        for i in range(5):
            time.sleep(0.01 * (i + 1))
            session.report({"i": i})

    ray.init(num_cpus=2, ignore_reinit_error=True)
    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1)).fit()
    finally:
        ray.shutdown()
    assert result.error is None
    history = result.metrics_history
    recent = result.metrics["_spans"]["session.report"]["recent"]
    assert len(recent) == 5 == len(history)
    starts = [start for start, _ in recent]
    assert starts == [r["_timestamp"] for r in history]
    assert all(start <= end for start, end in recent)
    assert [r["_training_iteration"] for r in history] == list(range(5))
    for i in range(1, 5):
        assert starts[i] - starts[i - 1] == history[i]["_time_this_iter_s"]
        assert history[i]["_time_this_iter_s"] >= 0.01 * (i + 1)
    # The first: since the session began, inside train.session_start.
    began = result.metrics["_spans"]["train.session_start"]
    first = starts[0] - history[0]["_time_this_iter_s"]
    assert began["first_start"] <= first <= began["last_end"]
    assert result.metrics["_timestamp"] == starts[-1]


def test_gc_pause_is_a_span_of_full_and_of_slow_collections(init2):
    import gc

    import jax  # noqa: F401 — watch_process is for a process with JAX

    from ray_tpu.util import tracing

    tracing.watch_process()
    tracing.watch_process()
    assert gc.callbacks.count(tracing._gc_phase) == 1
    gc.collect()  # leave little for the collections under test
    was = gc.isenabled()
    gc.disable()  # no collection of the interpreter's own choosing
    try:
        with tracing.collect() as got:
            gc.collect(0)  # young and fast: two callback calls, no span
            assert "gc.pause" not in got.summary
            before = time.time()
            gc.collect()
            after = time.time()
            with tracing.span("probe.next"):
                pass  # the store sees a pause with the next span closed
    finally:
        if was:
            gc.enable()
    pause = got.summary["gc.pause"]
    assert pause["count"] == 1
    (start, end), = pause["recent"]
    assert before <= start <= end <= after
    stored = [s for s in get_task_spans() if s["name"] == "gc.pause"
              and s["start"] == start]
    assert len(stored) == 1 and stored[0]["end"] == end
    assert stored[0]["args"] == {"generation": 2}


def test_gc_pause_reaches_every_open_collector():
    """A collection stops every thread: one forced on a second thread is
    in the first thread's ``collect()`` — the one span that is."""
    import gc
    import threading

    import jax  # noqa: F401

    from ray_tpu.util import tracing

    tracing.watch_process()
    theirs = {}

    def other():
        with tracing.collect() as got:
            with tracing.span("probe.theirs"):
                gc.collect()
        theirs.update(got.summary)

    was = gc.isenabled()
    gc.disable()
    try:
        with tracing.collect() as mine:
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        with tracing.collect() as later:
            pass
    finally:
        if was:
            gc.enable()
    assert mine.summary["gc.pause"]["count"] == 1
    assert mine.summary["gc.pause"]["recent"] \
        == theirs["gc.pause"]["recent"]
    assert "probe.theirs" not in mine.summary  # spans stay thread-local
    assert "gc.pause" not in later.summary


# ------------- a late step says whose fault it was: clocks, lags, flush --

def test_a_clocked_spans_clock_is_as_long_as_recent_and_survives_merge():
    """``span(name, clock=True)``: ``thread_clock()`` of each span's start
    beside its ``(start, end)`` — same length, same order, same bound —
    cumulative, and through ``merge`` as ``recent`` goes."""
    from ray_tpu.util import tracing

    with tracing.collect() as got:
        for i in range(300):
            with tracing.span("probe.clocked", clock=True):
                pass
            if i == 150:  # burn CPU on this thread: the clock must show it
                t0 = time.thread_time()
                while time.thread_time() - t0 < 0.05:
                    pass
        with tracing.span("probe.plain"):
            pass
    mine = got.summary["probe.clocked"]
    assert "clock" not in got.summary["probe.plain"]
    assert mine["count"] == 300 and tracing.RECENT == 256
    assert len(mine["clock"]) == len(mine["recent"]) == 256
    assert isinstance(mine["clock"], list)
    for clock in mine["clock"]:
        cpu, process_cpu, voluntary, involuntary, faults = clock
        assert 0 < cpu <= process_cpu
        assert all(isinstance(n, int) and n >= 0
                   for n in (voluntary, involuntary, faults))
    assert mine["clock"] == sorted(mine["clock"])  # cumulative, in order
    cpus = [c[0] for c in mine["clock"]]
    burnt = max(range(255), key=lambda i: cpus[i + 1] - cpus[i])
    assert burnt == 150 - 44 and cpus[burnt + 1] - cpus[burnt] >= 0.05

    # merge: the clocks stay with the spans they belong to
    early, late = tracing._Collected(), tracing._Collected()
    for i in range(200):
        late.add("probe.clocked", 1000.0 + i, 1000.5 + i, (1.0 + i, i))
    for i in range(100):
        early.add("probe.clocked", float(i), i + 0.5, (0.001 * i, -i))
    late.merge(early.summary)  # the older ones are folded in second
    late.merge({"probe.old": {  # a summary from before ``clock``
        "count": 1, "total_s": 1.0, "max_s": 1.0, "first_start": 0.0,
        "last_end": 1.0, "recent": [(0.0, 1.0)]}})
    merged = late.summary
    both = merged["probe.clocked"]
    assert both["count"] == 300
    assert len(both["recent"]) == len(both["clock"]) == 256
    assert both["recent"] == sorted(both["recent"])
    assert both["recent"][0] == (44.0, 44.5)
    assert both["clock"][0] == (0.001 * 44, -44)
    assert both["recent"][56] == (1000.0, 1000.5)
    assert both["clock"][56] == (1.0, 0) and both["clock"][-1] == (200.0, 199)
    assert "clock" not in merged["probe.old"]


def test_a_clocked_or_process_wide_span_never_imports_jax():
    import subprocess
    import sys

    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.collect() as got:\n"
            "    with tracing.span('a', clock=True):\n"
            "        pass\n"
            "    with tracing.span('b', process_wide=True, min_s=0.0):\n"
            "        pass\n"
            "    with tracing.span('c', process_wide=True, min_s=9.0):\n"
            "        pass\n"
            "    tracing.record('d', 1.0, 2.0, process_wide=True)\n"
            "assert len(got.summary['a']['clock'][0]) == 5\n"
            "assert set(got.summary) == {'a', 'b', 'd'}, got.summary\n"
            "assert 'jax' not in sys.modules, 'a span imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def _make_a_gc_pause():
    import gc

    gc.collect()


def _make_a_lag():
    import ctypes

    # one C call that does not release the interpreter's lock: a PyDLL's
    ctypes.PyDLL(None).usleep(100_000)
    time.sleep(0.05)  # the lag meter wakes, late, and records


def _make_a_flush():
    """A span made as ``decref_flusher`` makes its own (the periodic
    thread itself: ``test_worker_flush_is_the_periodic_threads_...``)."""
    from ray_tpu.util import tracing

    with tracing.span("worker.flush", process_wide=True,
                      min_s=tracing.GC_PAUSE_MIN_S) as s:
        time.sleep(0.002)
        s.args["slowest"] = "flush_spans"


@pytest.mark.parametrize("name,make", [
    ("gc.pause", _make_a_gc_pause), ("host.lag", _make_a_lag),
    ("worker.flush", _make_a_flush)])
def test_a_process_wide_span_reaches_every_open_collector_and_the_store(
        init2, name, make):
    """Made on a thread that collects nothing, it is in the collectors of
    two other threads and in none that opens later; and in the store."""
    import gc
    import threading

    import jax  # noqa: F401 — watch_process is for a process with JAX

    from ray_tpu.util import tracing

    tracing.watch_process()
    theirs, opened, done = {}, threading.Event(), threading.Event()

    def other():
        with tracing.collect() as got:
            opened.set()
            done.wait(timeout=60)
        theirs.update(got.summary)

    was = gc.isenabled()
    gc.disable()
    try:
        with tracing.collect() as mine:
            watcher = threading.Thread(target=other)
            watcher.start()
            assert opened.wait(timeout=30)
            deadline = time.time() + 30
            while name not in mine.summary and time.time() < deadline:
                maker = threading.Thread(target=make)
                maker.start()
                maker.join(timeout=30)
            done.set()
            watcher.join(timeout=30)
            with tracing.span("probe.next"):
                pass  # a pause is stored with the next span closed
        with tracing.collect() as later:
            pass
    finally:
        done.set()
        if was:
            gc.enable()
    assert mine.summary[name]["count"] >= 1
    assert mine.summary[name]["recent"][0] in theirs[name]["recent"]
    assert "probe.next" not in theirs  # other spans stay thread-local
    assert name not in later.summary
    start, end = mine.summary[name]["recent"][0]
    stored = [s for s in get_task_spans() if s["name"] == name
              and s["start"] == start]
    assert len(stored) == 1 and stored[0]["end"] == end
    if name == "host.lag":  # from where it should have woken to where it did
        assert tracing.LAG_MIN_S < end - start < 5.0
    if name == "worker.flush":
        assert stored[0]["args"] == {"slowest": "flush_spans"}


def test_process_wide_spans_from_many_threads_lose_and_double_nothing(init2):
    """More threads than cores, each closing process-wide spans of a name
    of its own — half of them the way the collector's callback does, left
    for the next span to store — into collectors that two other threads
    hold open, under a switch interval of 10 us: every collector counts
    every span once, and the store holds each once."""
    import sys
    import threading

    from ray_tpu.util import tracing

    threads, each, deferred = 12, 400, 60  # 6 x 60 < _unstored's 1024
    go, seen = threading.Event(), {}

    def emit(i):
        go.wait(timeout=30)
        for n in range(each if i % 2 else deferred):
            if i % 2:
                tracing.record(f"probe.wide.{i}", float(n), n + 0.5,
                               process_wide=True)
            else:
                tracing._emit(f"probe.wide.{i}", float(n), n + 0.5,
                              tracing.new_id(), None, None,
                              process_wide=True, store_later=True)

    def hold():
        with tracing.collect() as got:
            go.wait(timeout=30)
            for _ in range(each):
                with tracing.span("probe.own"):
                    pass
            for t in emitters:
                t.join(timeout=60)
        seen[threading.get_ident()] = got.summary

    emitters = [threading.Thread(target=emit, args=(i,))
                for i in range(threads)]
    holders = [threading.Thread(target=hold) for _ in range(2)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in emitters + holders:
            t.start()
        go.set()
        for t in emitters + holders:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    with tracing.span("probe.drain"):
        pass  # stores what the deferred half left
    assert len(seen) == 2
    want = {f"probe.wide.{i}": each if i % 2 else deferred
            for i in range(threads)}
    for summary in seen.values():
        assert summary["probe.own"]["count"] == each  # its own only
        assert {n: summary[n]["count"] for n in want} == want
    stored = {}
    for s in get_task_spans(limit=1_000_000):
        if s["name"] in want:
            stored[s["name"]] = stored.get(s["name"], 0) + 1
    assert stored == want
    assert not tracing._unstored


def test_watch_process_starts_one_lag_meter_however_often_it_is_called():
    import threading

    import jax  # noqa: F401

    from ray_tpu.util import tracing

    for _ in range(3):
        tracing.watch_process()
    meters = [t for t in threading.enumerate() if t.name == "ray_tpu-lag"]
    assert len(meters) == 1 and meters[0].daemon and meters[0].is_alive()
    assert (tracing.LAG_SLEEP_S, tracing.LAG_MIN_S) == (0.010, 0.020)


def test_worker_flush_is_the_periodic_threads_iteration_over_a_millisecond(
        init2):
    """In a real worker: 60000 spans make ``flush_spans`` take over a
    millisecond, and that iteration of ``decref_flusher`` is a
    ``worker.flush`` in the collector of the task's thread — another
    thread's — with its slowest call named; the quick iterations of an
    idle machine's idle second are none."""
    @ray.remote
    def busy():
        from ray_tpu.util import tracing

        with tracing.collect() as got:
            time.sleep(0.6)  # two iterations with nothing to do
            idle = got.summary.get("worker.flush", {"count": 0})["count"]
            deadline = time.time() + 20
            while time.time() < deadline and got.summary.get(
                    "worker.flush", {"count": 0})["count"] == idle:
                now = time.time()
                for _ in range(60000):
                    tracing.record("probe.bulk", now, now)
                time.sleep(0.3)
        return idle, got.summary["worker.flush"]

    idle, flush = ray.get(busy.remote(), timeout=120)
    # (idle is 0 unless the machine's other tenants held an idle
    # iteration up for a millisecond)
    assert flush["count"] > idle and flush["max_s"] >= 1e-3
    deadline = time.time() + 10  # the span itself rides the NEXT flush
    stored = []
    while not stored and time.time() < deadline:
        time.sleep(0.3)
        stored = [s for s in get_task_spans(limit=1_000_000)
                  if s["name"] == "worker.flush"]
    assert stored and stored[0]["worker_id"] != "driver"
    assert "flush_spans" in {s["args"]["slowest"] for s in stored}


# Run in a process of its own (one of them stops it): a loop that reports
# every 20 ms, twenty times, and before the eleventh report loses 0.3 s in
# one of five ways.  Prints ``{way: run}``, each ``run`` what the
# benchmark's readers take (``Result.metrics["_spans"]`` and the window).
# The machine is shared with five other test workers, whose pressure is the
# very thing these spans measure: a way whose part reads over 50 ms off is
# made again, at most three times, and ``tries`` says how often.
_STALLS = r"""
import json, os, signal, subprocess, sys, threading, time
import jax  # noqa: F401 — watch_process is for a process with JAX
from ray_tpu.util import tracing
from benchmark import lost_time

tracing.watch_process()
STALL = 0.3


# started before any loop, so that a stall pays for no fork: at each line
# it is sent it stops this process for STALL, and answers when that is over
stopper = subprocess.Popen([sys.executable, "-c",
    "import os, signal, sys, time\n"
    "for _ in sys.stdin:\n"
    "    os.kill(%d, signal.SIGSTOP); time.sleep(%r); "
    "os.kill(%d, signal.SIGCONT); print(flush=True)"
    % (os.getpid(), STALL, os.getpid())],
    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)


def stopped():
    stopper.stdin.write("stop\n")
    stopper.stdin.flush()
    stopper.stdout.readline()  # stopped in here, and back as it ends


def running():
    t0 = time.thread_time()
    while time.thread_time() - t0 < STALL:
        pass


def waiting():
    time.sleep(STALL)


def _on_another_thread(f):
    t = threading.Thread(target=f)
    t.start()
    return t


def python_spin():  # takes turns with the loop: stalls nothing
    def spin():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < STALL:
            pass
    _on_another_thread(spin)


def held():  # ONE C call that keeps the interpreter's lock for STALL
    import ctypes  # a PyDLL's calls are made WITH the lock held
    hold_for = _on_another_thread(
        lambda: ctypes.PyDLL(None).usleep(int(1e6 * STALL)))
    time.sleep(0.001)  # let it take the lock
    hold_for.join()


def one(stall):
    with tracing.collect() as got:
        with tracing.span("probe.warm"):
            pass  # the first span of a process imports the profiler's own
        start = time.time()
        for i in range(21):
            time.sleep(0.02)
            if i == 11:
                stall()
            with tracing.span("session.report", clock=True):
                pass
        elapsed = time.time() - start
    return {"process_start": start - 1.0,
            "worker": {"_spans": got.summary, "window_start": start,
                       "window": {"elapsed_s": elapsed}}}


PART = {"stopped": "stopped_ms", "running": "running_ms",
        "waiting": "waiting_ms", "held": "stopped_ms"}
out = {}
for stall in (stopped, running, waiting, python_spin, held):
    for tries in range(1, 4):
        run = one(stall)
        part, total = PART.get(stall.__name__), lost_time.totals(run)
        if part is None or (abs(total[part] - 1e3 * STALL) < 50
                            and total["late_ms"] - total[part] < 50):
            break
    run["tries"] = tries
    out[stall.__name__] = run
stopper.stdin.close()
stopper.wait(timeout=10)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def stalls():
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [root, os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run([sys.executable, "-c", _STALLS], env=env,
                          capture_output=True, text=True, timeout=180)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _reader(name):
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_reader", os.path.join(root, "benchmark", "layer_metrics",
                                name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("way,part", [
    ("stopped", "stopped"), ("running", "running"), ("waiting", "waiting"),
    ("held", "stopped")])
def test_a_synthetic_stall_of_300_ms_reads_as_what_it_was(stalls, way, part):
    """The process stopped (SIGSTOP, then SIGCONT from a helper) and a
    thread that keeps the interpreter through one C call read STOPPED —
    the lag meter sees both —; the loop thread spinning reads RUNNING; the
    loop thread in ``time.sleep``, what waiting for the device looks like
    from the host, reads WAITING.  Each within 50 ms of the 300, through
    the benchmark's readers, and on ONE interval: the eleventh."""
    from benchmark import lost_time

    run = stalls[way]
    late = _reader("host.late_ms")(run)
    parts = {p: _reader(f"host.late_{p}_ms")(run)
             for p in ("stopped", "running", "waiting")}
    assert parts["stopped"] + parts["running"] + parts["waiting"] == late
    # (the other two parts are the machine's: under five busy neighbours
    # a spin of 0.3 s of CPU takes 0.6 s of wall, and the rest reads
    # ``waiting`` — the helper tries thrice for a quiet reading of them)
    assert abs(parts[part] - 300.0) < 50.0, (parts, run["tries"])
    rows = lost_time.intervals(run)
    assert len(rows) == 20
    worst = max(rows, key=lambda r: r["late_s"])
    assert (worst["report"], worst["in_window"]) == (11, 11)
    assert worst[part + "_s"] == pytest.approx(parts[part] / 1e3, abs=0.05)
    if part == "stopped":  # (a busy machine may add a lag to the others)
        assert "host.lag" in worst["spans"]
    assert _reader("host.involuntary_switches")(run) >= 0
    assert _reader("worker.flush_ms")(run) == 0.0  # no worker here
    # (a lag before the window is the machine's other tenants': no fault)
    assert _reader("setup.lag_s")(run) >= 0.0


def test_python_threads_that_take_turns_are_no_lag_but_show_in_the_switches(
        stalls):
    """THE FINDING: a pure-Python spin on another thread is NOT seen by
    the lag meter — a waiting thread is handed the interpreter within the
    switch interval, 5 ms, under ``LAG_MIN_S`` — and stalls no report by
    more than that.  It shows in the loop thread's VOLUNTARY switches
    (each wait for the interpreter is one) and in the CPU time of the
    process's other threads."""
    from benchmark import lost_time

    spin, quiet = stalls["python_spin"], stalls["waiting"]
    total = lost_time.totals(spin)
    # (0.0, and no ``host.lag`` at all, on a machine with idle cores)
    assert total["stopped_ms"] < 50.0
    assert max(r["late_s"] for r in lost_time.intervals(spin)) < 0.15
    # the spin's 0.3 s outlast the window by a little: 0.18-0.25 s of it
    # are inside on an idle machine, less beside busy neighbours
    assert total["other_threads_cpu_s"] > 0.06
    assert lost_time.totals(quiet)["other_threads_cpu_s"] < 0.03
    # a quiet interval has one voluntary switch, the sleep; one spent
    # beside the spinner several
    assert max(r["voluntary"] for r in lost_time.intervals(spin)) >= 3


# ------------------------------------- device trace -> scope and phase --

def _xspace(ops, modules):
    """A one-chip trace as the profiler writes it (text proto ->
    bytes): ``ops`` are (instruction text, op_name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    meta, events = [], []
    for i, (text, op_name, start, dur) in enumerate(ops, start=1):
        stat = (f'stats {{ metadata_id: 1 str_value: "{op_name}:" }}'
                if op_name else "")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{text}" {stat} }} }}')
        events.append(f"events {{ metadata_id: {i} offset_ps: "
                      f"{start * 1000} duration_ps: {dur * 1000} }}")
    base = len(ops)
    mod_events = []
    for j, (start, dur) in enumerate(modules, start=1):
        meta.append(f'event_metadata {{ key: {base + j} value {{ '
                    f'id: {base + j} name: "jit_step(7)" }} }}')
        mod_events.append(f"events {{ metadata_id: {base + j} offset_ps: "
                          f"{start * 1000} duration_ps: {dur * 1000} }}")
    text = ('planes { id: 1 name: "/device:TPU:0" '
            'lines { id: 1 name: "XLA Modules" ' + " ".join(mod_events)
            + ' } lines { id: 2 name: "XLA Ops" ' + " ".join(events) + " } "
            + " ".join(meta)
            + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_step_breakdown_counts_the_stream_kernels_under_their_scopes(
        tmp_path):
    """The n-stream residual's halves (``ops/streams.py``): an ``hc_*``
    custom call is a kernel row under ITS name (the scope's own name
    starts the same way and is no kernel) with its calls a step, and its
    time is its scope's, in the phase its stack shows — the backward
    kernels under the forward operation's scope."""
    from ray_tpu.util.tracing import format_breakdown, step_breakdown

    fwd, bwd = _FWD, _BWD
    call = ', custom_call_target=\\"tpu_custom_call\\"'

    def kernel(scope, name):
        return f"{scope}/jit(_{name[3:]}_call)/{name}/pallas_call"

    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)]
    at = 2000
    for block in range(2):
        ops += [
            (f"%hc_read_fwd.{block} = bf16[] custom-call()" + call,
             fwd + kernel("hc_map", "hc_read_fwd"), at, 30),
            (f"%hc_write_fwd.{block} = bf16[] custom-call()" + call,
             fwd + kernel("hc_mix", "hc_write_fwd"), at + 30, 50),
            (f"%hc_read_fwd.{block + 2} = bf16[] custom-call()" + call,
             bwd + "rematted_computation/" + kernel("hc_map", "hc_read_fwd"),
             at + 80, 31),
            (f"%hc_write_bwd.{block} = bf16[] custom-call()" + call,
             bwd + kernel("hc_mix", "hc_write_bwd"), at + 111, 90),
            (f"%hc_read_bwd.{block} = bf16[] custom-call()" + call,
             bwd + kernel("hc_map", "hc_read_bwd"), at + 201, 80),
            (f"%pad.{block} = f32[] fusion()", bwd + "hc_map/pad", at + 281,
             4),
        ]
        at += 300
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "hc_read_fwd": 60, "hc_read_fwd.remat": 62, "hc_write_fwd": 100,
        "hc_write_bwd": 180, "hc_read_bwd": 160}
    assert b["kernel_calls"] == {
        "hc_read_fwd": 2, "hc_read_fwd.remat": 2, "hc_write_fwd": 2,
        "hc_write_bwd": 2, "hc_read_bwd": 2}
    assert {p: ns(t) for p, t in b["scopes"]["hc_map"].items()} == {
        "forward": 60, "remat": 62, "backward": 168}
    assert {p: ns(t) for p, t in b["scopes"]["hc_mix"].items()} == {
        "forward": 100, "backward": 180}
    assert b["unscoped_s"] == 0
    assert "hc_write_bwd  x 2 a step" in format_breakdown(b)


@pytest.mark.parametrize("first,backward,weight_grads", [
    ("moe_gmm_swiglu", ("moe_gmm_dswiglu", "moe_gmm_pair"), 3),
    ("moe_gmm_relu2", ("moe_gmm_drelu2", "moe_gmm"), 2)],
    ids=["swiglu", "relu2"])
def test_step_breakdown_names_the_expert_ffn_kernels(tmp_path, first,
                                                     backward, weight_grads):
    """The routed experts' FFN (``ops/moe.py::expert_ffn``): each of its
    kernels is a row under ITS name — ``moe_gmm_swiglu`` is not filed
    under ``moe_gmm`` — with its calls a step, the rerun's apart, and
    all of their time is the scope ``moe_experts``'.  Of the expert without
    a gate the rows are ``moe_gmm_relu2`` and ``moe_gmm_drelu2``, the rows'
    gradient one more plain ``moe_gmm`` and the weights' two ``moe_tgmm``."""
    from ray_tpu.util.tracing import format_breakdown, step_breakdown

    call = ', custom_call_target=\\"tpu_custom_call\\"'
    times = {first: 40, "moe_gmm": 30, backward[0]: 35, "moe_tgmm": 20}
    times.setdefault(backward[1], 45)

    def kernel(prefix, name, n, at):
        return (f"%{name}.{n} = bf16[] custom-call()" + call,
                f"{prefix}moe_experts/{name}/pallas_call", at, times[name])

    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)]
    at = 2000
    for layer in range(2):
        ops += [kernel(_FWD, first, layer, at),
                kernel(_FWD, "moe_gmm", layer, at + 40),
                kernel(_BWD + "rematted_computation/", first,
                       layer + 2, at + 70),
                kernel(_BWD + "rematted_computation/", "moe_gmm", layer + 2,
                       at + 110),
                kernel(_BWD, backward[0], layer, at + 140),
                kernel(_BWD, backward[1], layer + 4, at + 175)]
        ops += [kernel(_BWD, "moe_tgmm", 3 * layer + n, at + 220 + 20 * n)
                for n in range(weight_grads)]
        at += 300
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    calls = {first: 2, "moe_gmm": 2, first + ".remat": 2,
             "moe_gmm.remat": 2, backward[0]: 2,
             "moe_tgmm": 2 * weight_grads}
    calls[backward[1]] = calls.get(backward[1], 0) + 2
    assert b["kernel_calls"] == calls
    ns = lambda s: round(s * 1e9)  # noqa: E731
    rows_grad = times[backward[1]]
    assert {p: ns(t) for p, t in b["scopes"]["moe_experts"].items()} == {
        "forward": 140, "remat": 140,
        "backward": 2 * (35 + rows_grad + 20 * weight_grads)}
    assert ns(sum(b["kernels"].values())) == 280 + 2 * (
        35 + rows_grad + 20 * weight_grads)
    text = format_breakdown(b)
    assert f"{backward[0]}  x 2 a step" in text
    assert f"moe_tgmm  x {2 * weight_grads} a step" in text


# op_name prefixes of the layer scan's two loops, and the text of a flash
# kernel's instruction at the s4096 cell's shapes.
_FWD = "jit(step)/jvp()/while/body/closed_call/"
_BWD = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
_KERNEL = (', custom_call_target=\\"tpu_custom_call\\", '
           'operand_layout_constraints={bf16[4,32,4096,128]{3,2,1,0}, '
           'bf16[4,32,4096,128]{3,2,1,0}, bf16[4,32,4096,128]{3,2,1,0}}')


def test_step_breakdown_on_a_synthetic_trace(tmp_path):
    from ray_tpu.train.core import STEP_SCOPES
    from ray_tpu.util.tracing import (
        format_breakdown, scope_and_phase, step_breakdown)

    fwd, bwd, kernel = _FWD, _BWD, _KERNEL
    # One step of 1000 ns from t=2000 (the first execution is a lead-in):
    # a forward ``while`` [2000, 2400) over three ops, its own 100 ns left;
    # then a backward while [2500, 2900) holding a remat op, a nested
    # while, two more ops and 50 ns of its own.
    ops = [
        ("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100),
        ("%while.1 = () while()", "jit(step)/jvp()/while", 2000, 400),
        ("%qkv.1 = f32[] fusion()", fwd + "attn_qkv/dot_general", 2000, 100),
        ("%flash_fwd.1 = f32[] custom-call()" + kernel,
         fwd + "attention/flash_fwd/pallas_call", 2100, 100),
        ("%ffn.1 = f32[] fusion()", fwd + "ffn/dot_general", 2200, 100),
        ("%head.1 = f32[] fusion()", "jit(step)/jvp(lm_head)/dot_general",
         2400, 50),
        ("%loss.1 = f32[] fusion()",
         "jit(step)/jvp(loss)/jit(log_softmax)/sub", 2450, 50),
        ("%while.2 = () while()", "jit(step)/transpose(jvp())/while",
         2500, 400),
        ("%flash_fwd.2 = f32[] custom-call()" + kernel,
         bwd + "rematted_computation/attention/flash_fwd/pallas_call",
         2500, 50),
        ("%while.3 = () while()", bwd + "attention/flash_dkv/while",
         2550, 200),
        ("%flash_dkv.1 = f32[] custom-call()" + kernel,
         bwd + "attention/flash_dkv/pallas_call", 2550, 120),
        ("%out.1 = f32[] fusion()", bwd + "attn_out/transpose", 2750, 50),
        ("%slice.1 = f32[] fusion()",
         "jit(step)/transpose(jvp())/while/body/squeeze", 2800, 50),
        ("%adam.1 = f32[] fusion()", "jit(step)/optimizer/mul", 2900, 50),
        ("%copy.9 = f32[] copy()", "", 2950, 30),
        ("%embed.1 = f32[] gather()", "jit(step)/jvp(embed)/gather",
         2980, 20),
    ]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    assert b["steps"] == 1 and b["device"] == 0
    ns = lambda s: round(s * 1e9)  # noqa: E731
    got = {scope: {p: ns(t) for p, t in row.items()}
           for scope, row in b["scopes"].items()}
    assert got == {
        "attn_qkv": {"forward": 100},
        "attention": {"forward": 100, "remat": 50, "backward": 200},
        "ffn": {"forward": 100},
        "lm_head": {"forward": 50},
        "loss": {"forward": 50},
        "attn_out": {"backward": 50},
        "embed": {"forward": 20},
        "optimizer": {"optimizer": 50},
        # The scan's own: 100 ns of while.1, 50 of while.2, the slice.
        "scan": {"forward": 100, "backward": 100},
    }
    assert ns(b["unscoped_s"]) == 30
    assert b["unscoped_ops"] == [["copy.9", pytest.approx(30e-9)]]
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "flash_fwd": 100, "flash_fwd.remat": 50, "flash_dkv": 120}
    # Beside each kernel row, what its schedule computes over what the
    # causal mask leaves at the operands' shapes: static, from the text.
    assert b["kernel_pairs"] == {k: pytest.approx(1.0622, abs=1e-4)
                                 for k in b["kernels"]}
    assert "flash_dkv  executed/causal 1.0622" in format_breakdown(b)
    assert ns(b["step_s"]) == 1000
    total = sum(t for row in got.values() for t in row.values()) + 30
    assert total == ns(b["busy_s"]) == 1000  # the parts sum to the busy time
    assert set(got) - {"scan"} <= set(STEP_SCOPES)
    assert "attention" in format_breakdown(b)
    # No second execution of the module: nothing to read.
    path.write_bytes(_xspace(ops, modules=[(2000, 1000)]))
    assert step_breakdown(str(path), "jit_step") is None
    # The phase rule, once.
    assert scope_and_phase("jit(step)/jvp(lm_head)/dot_general",
                           STEP_SCOPES) == ("lm_head", "forward")
    assert scope_and_phase("jit(step)/transpose(jvp(lm_head))/dot_general",
                           STEP_SCOPES) == ("lm_head", "backward")
    assert scope_and_phase(bwd + "rematted_computation/ffn/dot_general",
                           STEP_SCOPES) == ("ffn", "remat")
    assert scope_and_phase("jit(step)/optimizer/add",
                           STEP_SCOPES) == ("optimizer", "optimizer")
    assert scope_and_phase("", STEP_SCOPES) == (None, "forward")


# The two calls as a compiled step holds them where the kernels read q,
# k, v in the model's own (b, s, heads x d) — mistral7b-train-s4096's
# shapes, 32 q heads over 8 KV heads of 128: the q heads' count stands in
# the stats alone (a result of flash_fwd, lane-replicated; operands of the
# ONE backward kernel, a float a row, whose results are dq, dk and dv).
_IN_PLACE = ('operand_layout_constraints={bf16[4,4096,4096]{2,1,0}, '
             'bf16[4,4096,1024]{2,1,0}, bf16[4,4096,1024]{2,1,0}')
_IN_PLACE_CALLS = {
    "flash_fwd": ("%flash_fwd.1 = (bf16[4,4096,4096]{2,1,0:T(8,128)(2,1)}, "
                  "f32[4,32,4096,128]{3,2,1,0:T(8,128)}) custom-call(%a, %b, "
                  '%c), custom_call_target=\\"tpu_custom_call\\", '
                  + _IN_PLACE + "}"),
    "flash_dkv": ("%flash_dkv.1 = (bf16[4,4096,4096]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[4,4096,1024]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[4,4096,1024]{2,1,0:T(8,128)(2,1)}) custom-call(%a), "
                  'custom_call_target=\\"tpu_custom_call\\", '
                  + _IN_PLACE + ", bf16[4,4096,4096]{2,1,0}, "
                  "f32[4,32,1,4096]{3,2,1,0}, f32[4,32,1,4096]{3,2,1,0}}"),
}


def test_executed_over_causal_reads_operands_where_the_model_leaves_them(
        tmp_path):
    """``flash_executed_over_causal`` on both forms of a call's operands:
    (b, h, s, d) as always; (b, s, h x d) with sq, sk from dimension 1 and
    the head size from the width over the stats' head count — the same
    ratio for the same call, and the ``step-breakdown`` rows keep their
    ``executed/causal`` column."""
    from ray_tpu.ops.attention import causal_tile_counts, choose_tiles
    from ray_tpu.util.tracing import (
        flash_executed_over_causal, format_breakdown, step_breakdown)

    want = flash_executed_over_causal(_KERNEL.replace("\\", ""))
    assert want == pytest.approx(1.0622, abs=1e-4)
    for text in _IN_PLACE_CALLS.values():
        assert flash_executed_over_causal(text.replace("\\", "")) == want
    # a head of 512 lanes halves the fetch tile: the width is not the head
    wide = ("operand_layout_constraints={f32[1,8192,2048]{2,1,0}, "
            "f32[1,4096,512]{2,1,0}, f32[1,4096,512]{2,1,0}, "
            "f32[1,4,1,8192]{3,2,1,0}}")
    tiles = choose_tiles(8192, 4096, True, 512, "float32")
    assert tiles[0] == 1024
    n = causal_tile_counts(8192, 4096, *tiles)
    assert flash_executed_over_causal(wide) == (
        n["executed_pairs"] / n["causal_pairs"])
    # no stats to take the heads from, a dtype no kernel takes: nothing
    assert flash_executed_over_causal(_IN_PLACE + "}") is None
    assert flash_executed_over_causal(
        _IN_PLACE_CALLS["flash_dkv"].replace("bf16", "s8")) is None
    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)] + [
        (text, (_FWD if name == "flash_fwd" else _BWD)
         + f"attention/{name}/pallas_call", 2000 + 100 * i, 100)
        for i, (name, text) in enumerate(_IN_PLACE_CALLS.items())]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    assert b["kernel_pairs"] == {name: want for name in _IN_PLACE_CALLS}
    assert "flash_dkv  executed/causal 1.0622" in format_breakdown(b)


def test_step_breakdown_without_a_rematerialised_kernel(tmp_path):
    """A step whose checkpoint keeps the flash kernel's residuals: the
    rematerialised attention holds a transpose and no kernel, so there is
    no ``flash_fwd.remat`` row — none is assumed, printing included."""
    from ray_tpu.util.tracing import format_breakdown, step_breakdown

    fwd, bwd, kernel = _FWD, _BWD, _KERNEL
    ops = [
        ("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100),
        ("%flash_fwd.1 = f32[] custom-call()" + kernel,
         fwd + "attention/flash_fwd/pallas_call", 2000, 100),
        ("%copy.1 = f32[] copy()",
         bwd + "rematted_computation/attention/transpose", 2100, 30),
        ("%flash_dkv.1 = f32[] custom-call()" + kernel,
         bwd + "attention/flash_dkv/pallas_call", 2200, 120),
    ]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "flash_fwd": 100, "flash_dkv": 120}
    assert set(b["kernel_pairs"]) == {"flash_fwd", "flash_dkv"}
    assert {p: ns(t) for p, t in b["scopes"]["attention"].items()} == {
        "forward": 100, "remat": 30, "backward": 120}
    text = format_breakdown(b)
    assert "flash_fwd  executed/causal 1.0622" in text
    assert ".remat" not in text
    # A step with no kernel at all (reference attention) prints too.
    path.write_bytes(_xspace(ops[:1] + ops[2:3],
                             modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    assert b["kernels"] == {} and b["kernel_pairs"] == {}
    assert "kernel" not in format_breakdown(b)


def test_step_breakdown_counts_the_state_space_kernels(tmp_path):
    """A Mamba-2 layer's scan (``ops/ssm.py``): kernel rows ``ssd_fwd``
    (forward pass, and rematerialised: its states are not kept) and
    ``ssd_bwd`` with their calls a step — layers x passes — beside the
    flash rows; their time is the ``ssm_scan`` scope's with the XLA ops
    around them."""
    from ray_tpu.util.tracing import format_breakdown, step_breakdown

    fwd, bwd = _FWD, _BWD
    call = ', custom_call_target=\\"tpu_custom_call\\"'
    scan = "ssm_scan/jit(_fwd_call)/ssd_fwd/pallas_call"
    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)]
    at = 2000
    for layer in range(3):
        ops += [
            (f"%cumsum.{layer} = f32[] fusion()",
             fwd + "ssm_scan/cumsum", at, 5),
            (f"%ssd_fwd.{layer} = bf16[] custom-call()" + call,
             fwd + scan, at + 5, 40),
            (f"%ssd_fwd.{layer + 3} = bf16[] custom-call()" + call,
             bwd + "rematted_computation/" + scan, at + 45, 41),
            (f"%ssd_bwd.{layer} = bf16[] custom-call()" + call,
             bwd + "ssm_scan/jit(_bwd_call)/ssd_bwd/pallas_call",
             at + 86, 100),
        ]
        at += 200
    ops.append(("%flash_fwd.1 = f32[] custom-call()" + _KERNEL,
                fwd + "attention/flash_fwd/pallas_call", at, 50))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "ssd_fwd": 120, "ssd_fwd.remat": 123, "ssd_bwd": 300,
        "flash_fwd": 50}
    assert b["kernel_calls"] == {"ssd_fwd": 3, "ssd_fwd.remat": 3,
                                 "ssd_bwd": 3, "flash_fwd": 1}
    assert set(b["kernel_pairs"]) == {"flash_fwd"}
    assert {p: ns(t) for p, t in b["scopes"]["ssm_scan"].items()} == {
        "forward": 135, "remat": 123, "backward": 300}
    text = format_breakdown(b)
    assert "ssd_bwd  x 3 a step" in text
    assert "flash_fwd  executed/causal 1.0622  x 1 a step" in text


@pytest.mark.parametrize("scope", ["ssm_conv", "gdn_conv", "kda_conv"])
def test_step_breakdown_counts_the_convolutions_kernels(tmp_path, scope):
    """The mixers' causal convolution (``ops/ssm.py::causal_conv1d``):
    kernel rows ``causal_conv_fwd`` (the pass, and its rerun under the
    layer checkpoint) and ``causal_conv_bwd`` with their calls a step,
    their time the conv scope's with the XLA ops round them.  The prefix
    ``causal_conv_`` starts no step scope, so a scope is no kernel's
    name."""
    from ray_tpu.train.core import STEP_SCOPES
    from ray_tpu.util.tracing import (
        KERNEL_NAMES, format_breakdown, step_breakdown)

    assert "causal_conv_" in KERNEL_NAMES and scope in STEP_SCOPES
    assert not [s for s in STEP_SCOPES if s.startswith("causal_conv_")
                or "causal_conv_fwd".startswith(s)]
    fwd, bwd = _FWD, _BWD
    call = ', custom_call_target=\\"tpu_custom_call\\"'
    conv = scope + "/jit(_conv_fwd_call)/causal_conv_fwd/pallas_call"
    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)]
    at = 2000
    for layer in range(3):
        ops += [
            (f"%causal_conv_fwd.{layer} = bf16[] custom-call()" + call,
             fwd + conv, at, 9),
            (f"%norm.{layer} = f32[] fusion()", fwd + scope + "/mul",
             at + 9, 5),
            (f"%causal_conv_fwd.{layer + 3} = bf16[] custom-call()" + call,
             bwd + "rematted_computation/" + conv, at + 14, 10),
            (f"%causal_conv_bwd.{layer} = bf16[] custom-call()" + call,
             bwd + scope + "/jit(_conv_bwd_call)/causal_conv_bwd/pallas_call",
             at + 24, 20),
        ]
        at += 200
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "causal_conv_fwd": 27, "causal_conv_fwd.remat": 30,
        "causal_conv_bwd": 60}
    assert set(b["kernel_calls"].values()) == {3}
    assert {p: ns(t) for p, t in b["scopes"][scope].items()} == {
        "forward": 42, "remat": 30, "backward": 60}
    text = format_breakdown(b)
    assert "causal_conv_fwd.remat  x 3 a step" in text
    assert "causal_conv_bwd  x 3 a step" in text


def test_step_breakdown_counts_the_delta_rule_kernels(tmp_path):
    """A gated delta-rule layer's rule (``ops/delta.py``): kernel rows
    ``delta_fwd`` (forward pass, and rematerialised: its entering states
    and inverses are not kept) and ``delta_bwd`` with their calls a step —
    linear layers x passes — beside the flash rows of the full-attention
    layer; their time is the ``gdn_scan`` scope's with the XLA ops round
    them (the layouts the kernels read, the cumulative log-decays).  The
    scope ``gdn_scan`` is no kernel's name: the kernels start ``delta_``."""
    from ray_tpu.util.tracing import (
        KERNEL_NAMES, format_breakdown, step_breakdown)

    assert "delta_" in KERNEL_NAMES and not "gdn_scan".startswith(
        KERNEL_NAMES)
    fwd, bwd = _FWD, _BWD
    # operands (b, heads, d, s), four-dimensional as a flash kernel's are:
    # the causal-pairs ratio is the flash rows' alone
    call = (', custom_call_target=\\"tpu_custom_call\\", '
            'operand_layout_constraints={bf16[1,30,96,4096]{3,2,1,0}, '
            'bf16[1,30,96,4096]{3,2,1,0}, bf16[1,30,192,4096]{3,2,1,0}}')
    rule = "gdn_scan/jit(_fwd_call)/delta_fwd/pallas_call"
    ops = [("%lead = f32[] add()", "jit(step)/optimizer/add", 500, 100)]
    at = 2000
    for layer in range(3):
        ops += [
            (f"%copy.{layer} = bf16[] copy()",
             fwd + "gdn_scan/transpose", at, 7),
            (f"%delta_fwd.{layer} = bf16[] custom-call()" + call,
             fwd + rule, at + 7, 60),
            (f"%delta_fwd.{layer + 3} = bf16[] custom-call()" + call,
             bwd + "rematted_computation/" + rule, at + 67, 61),
            (f"%delta_bwd.{layer} = bf16[] custom-call()" + call,
             bwd + "gdn_scan/jit(_bwd_call)/delta_bwd/pallas_call",
             at + 128, 35),
        ]
        at += 200
    ops.append(("%flash_fwd.1 = f32[] custom-call()" + _KERNEL,
                fwd + "attention/flash_fwd/pallas_call", at, 50))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules=[(0, 1000), (2000, 1000)]))
    b = step_breakdown(str(path), "jit_step")
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert {k: ns(t) for k, t in b["kernels"].items()} == {
        "delta_fwd": 180, "delta_fwd.remat": 183, "delta_bwd": 105,
        "flash_fwd": 50}
    assert b["kernel_calls"] == {"delta_fwd": 3, "delta_fwd.remat": 3,
                                 "delta_bwd": 3, "flash_fwd": 1}
    assert set(b["kernel_pairs"]) == {"flash_fwd"}
    assert {p: ns(t) for p, t in b["scopes"]["gdn_scan"].items()} == {
        "forward": 201, "remat": 183, "backward": 105}
    text = format_breakdown(b)
    assert "delta_fwd.remat  x 3 a step" in text
    assert "delta_bwd  x 3 a step" in text
    assert "flash_fwd  executed/causal 1.0622  x 1 a step" in text
