"""Spans: what the program did and when, from ``fit()`` down to the chip.

Reference: ``ray timeline`` (``python/ray/scripts/scripts.py:1840`` — dumps
profiling events as chrome://tracing JSON) + the task-event span pipeline of
``python/ray/util/tracing/tracing_helper.py:164``.

One primitive, ``span(name, **args)``, records name, start, end, its own id
and the id of the span that CAUSED it: the enclosing span of the thread, or
for a task's root span (``worker_main._execute``) the span that submitted
the task, carried in the task spec with the submit time — so a task's wait
is ``start - submitted``, a field.  Spans go where task spans always went:
a worker's buffer, the periodic ``spans`` message, the head's deque,
``timeline()``.  The driver records straight into the head's store.
``record(name, start, end)`` is the same for an interval that is already
over; ``watch_process()`` turns what JAX reports of its compile pipeline
(``jax.trace``, ``jax.lower``, ``jax.compile``, ``jax.cache_load``,
``jax.cache_miss``) into such spans, and the garbage collector's pauses
(``gc.pause``) and the stretches in which no Python thread of the process
could run (``host.lag``) into PROCESS-WIDE ones: a span marked so says
something of every thread, and goes to every collector open in the process
whichever thread opened it (``_emit``; the worker's periodic thread,
``worker.flush``, is the third).  A span made with ``clock=True`` samples
the CPU clocks and the scheduler's counters of its thread as it opens
(``session.report`` does: ``_Collected``).  A train worker that was
granted chips opens them under ``device.bring_up`` (``chips=``) >
``jax.import``, ``jax.backend_init`` before the user's loop
(``train/backend.py::bring_up``) and is watched from between the two:
from the first program it makes.

The shared clock with the chip: when ``jax`` is ALREADY imported in the
process, a span also enters ``jax.profiler.TraceAnnotation(name)``, so under
``jax.profiler.start_trace`` it is an event of the ``/host:CPU`` plane of the
same ``.xplane.pb`` as the device's ops (``start_ns`` there counts from the
stat ``profile_start_time`` of the plane ``Task Environment``, which is
``time.time()`` in ns; a span that opened before the session started is not
recorded by the profiler).  This module never imports JAX itself: a driver
that must stay off the chip stays off it.

``step_breakdown`` reduces such a trace to device seconds per step by
``train.core.STEP_SCOPES`` scope and phase — the operator's answer to "which
part of the step is this op".
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import re
import resource
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ray_tpu._private.api_internal import get_runtime, require_runtime

_PROCESS = os.urandom(4).hex()  # span ids are unique across processes
_ids = itertools.count(1)
_local = threading.local()  # .stack: open span ids; .collectors: _Collected
# Every collector open in the process, whichever thread opened it: where a
# process-wide span goes (``_emit``).
_open_collectors: List["_Collected"] = []
# Process-wide spans the store has not seen yet: ``record_span``'s tuples
# less the task id (``_emit``).
_unstored: collections.deque = collections.deque(maxlen=1024)
RECENT = 256  # (start, end) pairs a collector keeps per name
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)  # Linux


def new_id() -> str:
    return f"{_PROCESS}-{next(_ids):x}"


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def current_span() -> Optional[str]:
    """Id of the innermost open span of this thread."""
    stack = _stack()
    return stack[-1] if stack else None


def stamp(spec: dict) -> None:
    """Mark a task spec with its cause and its submit time (submitter's
    clock); the executing worker's root span takes both over."""
    cause = current_span()
    spec["span"] = (cause, time.time())
    if cause is not None:
        for got in getattr(_local, "collectors", ()):
            got.causes.add(cause)


def thread_clock() -> Tuple[float, float, int, int, int]:
    """What a clocked span keeps of the calling thread as it opens, all
    cumulative: CPU seconds THIS thread has run, CPU seconds of all the
    process's threads (``time.process_time``), and the thread's voluntary
    context switches, involuntary ones and major page faults.  All but
    the second from ONE ``getrusage(RUSAGE_THREAD)`` — its ``ru_utime +
    ru_stime`` is ``time.thread_time()`` to the microsecond on Linux —
    because each is a system call: a microsecond the two on a plain
    Linux, 6 us EACH on the sandboxed host of the benchmark's chips,
    whose kernel also ticks CPU time in 10 ms and counts no switches
    (``PERF.md`` §6, PR 68).  Without ``RUSAGE_THREAD``: the two clocks
    and zeros."""
    if _RUSAGE_THREAD is None:
        return time.thread_time(), time.process_time(), 0, 0, 0
    ru = resource.getrusage(_RUSAGE_THREAD)
    return (ru.ru_utime + ru.ru_stime, time.process_time(), ru.ru_nvcsw,
            ru.ru_nivcsw, ru.ru_majflt)


class span:
    """``with span("train.backend_start", workers=4): ...``

    ``clock=True``: the span keeps ``thread_clock()`` of its start beside
    its ``(start, end)``.  ``process_wide=True``: it says something of
    every thread of the process and goes to every open collector.
    ``min_s``: a shorter one is dropped as it closes (its
    ``TraceAnnotation`` stands)."""

    __slots__ = ("name", "args", "id", "parent", "submitted", "task_id",
                 "kind", "start", "_annotation", "clock", "process_wide",
                 "min_s")

    def __init__(self, name: str, *, clock: bool = False,
                 process_wide: bool = False, min_s: float = 0.0, **args):
        self.name = name
        self.args = args
        self.id = new_id()
        self.parent = self.submitted = self._annotation = None
        self.task_id = b""
        self.kind = "span"
        self.clock = clock
        self.process_wide = process_wide
        self.min_s = min_s

    def __enter__(self):
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.id)
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)  # None mid-import too
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.clock = thread_clock() if self.clock else None
        self.start = time.time()
        return self

    def __exit__(self, *exc_info):
        end = time.time()
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
        _stack().pop()
        if end - self.start >= self.min_s:
            _emit(self.name, self.start, end, self.id, self.parent,
                  self.args, self.task_id, self.kind, self.submitted,
                  self.clock, self.process_wide)
        return False


def _emit(name: str, start: float, end: float, sid: str,
          parent: Optional[str], args: Optional[dict],
          task_id: bytes = b"", kind: str = "span",
          submitted: Optional[float] = None,
          clock: Optional[tuple] = None, process_wide: bool = False,
          store_later: bool = False) -> None:
    """A closed span goes to this thread's collectors — a process-wide
    one to EVERY open collector, the one place where a collector is not
    thread-local — and to the runtime's store (a worker files it under
    the task it is running).  ``store_later``: the caller may hold the
    store's lock (``_gc_phase``), so the span waits in ``_unstored`` for
    the next one this process closes."""
    for got in (list(_open_collectors) if process_wide
                else getattr(_local, "collectors", ())):
        got.add(name, start, end, clock)
    rec = (name, start, end, kind, sid, parent, submitted, args or None)
    if store_later:
        _unstored.append(rec)
        return
    rt = get_runtime()
    if rt is not None:
        if not task_id and rt.is_worker() \
                and rt.current_task_id is not None:
            task_id = rt.current_task_id.binary()
        while _unstored:
            try:
                rt.record_span((task_id, *_unstored.popleft()))
            except IndexError:  # another thread filed it
                break
        rt.record_span((task_id, *rec))


def record(name: str, start: float, end: float, *,
           process_wide: bool = False, **args) -> None:
    """A span reported after the fact: ``start`` and ``end`` are known, on
    ``time.time()``.  The same record as a ``with span(...)`` block makes
    (own id, this thread's innermost open span as cause, the task id,
    collectors — every open one for a ``process_wide`` span —, the
    runtime's store), but no ``TraceAnnotation``: it is over."""
    _emit(name, start, end, new_id(), current_span(), args,
          process_wide=process_wide)


def task_span(task: dict) -> span:
    """The root span of one task or actor call: caused by the span that
    submitted it, wherever that was."""
    s = span(task.get("name", "task"))
    s.task_id = task["task_id"]
    s.kind = "actor_method" if "actor_id" in task else "task"
    s.parent, s.submitted = task.get("span") or (None, None)
    return s


def span_record(rec: tuple, worker_id: str, node_id: str) -> Dict[str, Any]:
    """A ``spans`` message entry as the head stores it."""
    tid, name, start, end, kind, sid, parent, submitted, args = rec
    out = {"task_id": tid.hex(), "name": name, "start": start, "end": end,
           "kind": kind, "worker_id": worker_id, "node_id": node_id,
           "span_id": sid, "parent": parent}
    if submitted is not None:
        out["submitted"] = submitted
    if args:
        out["args"] = args
    return out


# ------------------------------------------------------------ summaries --

class _Collected:
    """Per-name totals of the spans a thread closed while collecting, and
    the ``(start, end)`` of each name's last ``RECENT`` spans (``recent``).
    A name whose spans are clocked (``span(..., clock=True)``: all of a
    name's are, or none) also keeps ``clock``: ``thread_clock()`` at each
    of those spans' starts, one tuple a span, as long as ``recent``, in its
    order and under its bound — cumulative values, so a reader takes the
    differences of neighbours."""

    def __init__(self):
        # Ids of this thread's spans under which a task or an actor was
        # submitted: what add_caused asks the head about.
        self.causes: set = set()
        self._names: Dict[str, Dict[str, Any]] = {}

    def _fold(self, name: str, count: int, total_s: float, max_s: float,
              first_start: float, last_end: float) -> Dict[str, Any]:
        s = self._names.get(name)
        if s is None:
            s = self._names[name] = {
                "count": count, "total_s": total_s, "max_s": max_s,
                "first_start": first_start, "last_end": last_end,
                "recent": collections.deque(maxlen=RECENT)}
        else:
            s["count"] += count
            s["total_s"] += total_s
            s["max_s"] = max(s["max_s"], max_s)
            s["first_start"] = min(s["first_start"], first_start)
            s["last_end"] = max(s["last_end"], last_end)
        return s

    def add(self, name: str, start: float, end: float,
            clock: Optional[tuple] = None):
        dur = end - start
        s = self._fold(name, 1, dur, dur, start, end)
        s["recent"].append((start, end))
        if clock is not None:
            s.setdefault("clock", collections.deque(maxlen=RECENT)).append(
                clock)

    def merge(self, summary: Optional[Dict[str, Dict[str, Any]]]):
        """Fold in another summary (a worker session's)."""
        for name, s in (summary or {}).items():
            mine = self._fold(name, s["count"], s["total_s"], s["max_s"],
                              s["first_start"], s["last_end"])
            recent = mine["recent"]
            both = [*recent, *map(tuple, s.get("recent", ()))]
            if "clock" in s:  # sorted with the spans they belong to
                clock = mine.setdefault(
                    "clock", collections.deque(maxlen=RECENT))
                pairs = sorted(zip(both, [*clock, *map(tuple, s["clock"])]))
                both = [p[0] for p in pairs]
                clock.clear()
                clock.extend(p[1] for p in pairs)
            else:
                both.sort()
            recent.clear()
            recent.extend(both)  # the deque keeps the newest RECENT

    @property
    def summary(self) -> Dict[str, Dict[str, Any]]:
        """name -> ``count``, ``total_s``, ``max_s``, ``first_start``,
        ``last_end`` and ``recent``, a list of ``(start, end)``, oldest
        first; a clocked name's ``clock`` beside it."""
        return {name: {k: list(v) if isinstance(v, collections.deque) else v
                       for k, v in s.items()}
                for name, s in list(self._names.items())}

    def add_caused(self):
        """Fold in the head's own spans that one of OUR spans caused:
        ``sched.wait`` and ``worker.spawn`` of the actors this thread
        created.  (Task spans it caused arrive with the workers' next
        flush, too late to count on here.)"""
        if not self.causes or get_runtime() is None:
            return
        for s in get_task_spans(parents=self.causes):
            if s["kind"] == "head":
                self.add(s["name"], s["start"], s["end"])


@contextlib.contextmanager
def collect():
    """Summarise every span this thread closes inside the block (and
    every process-wide span of any thread: it is about this one too)."""
    got = _Collected()
    active = _local.__dict__.setdefault("collectors", [])
    active.append(got)
    _open_collectors.append(got)
    try:
        yield got
    finally:
        _open_collectors.remove(got)
        active.remove(got)


# ------------------------------------ JAX's pipeline and the collector --

# jax.monitoring's events (jax 0.9, ``jax/_src/dispatch.py``) -> span name.
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_CACHE_MISS = "/jax/compilation_cache/cache_misses"
GC_PAUSE_MIN_S = 1e-3  # a younger generation's collection is a span from here
LAG_SLEEP_S = 10e-3  # the lag meter sleeps this long at a time
LAG_MIN_S = 20e-3  # woken later than this, it records a ``host.lag``


def _jax_open(event: str, start: float, fun_name: str = "", **_):
    """Scalar listener: ``log_elapsed_time.__enter__`` reports each
    pipeline stage's start.  A stage takes an id and opens — so what runs
    inside it (a cache load, an eager op's own compile) is its child —
    unless it only deepens one that is open: an inner ``jit``'s trace
    inside its caller's, or the trace of a helper that a lowering rule
    calls (lowering's time, hundreds of them a Pallas kernel)."""
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    opened = _local.__dict__.setdefault("jax_open", [])
    outer = [e[0] for e in opened if e[1] is not None]
    if name in outer or (name == "jax.trace" and "jax.lower" in outer):
        opened.append((name, None, None, fun_name))
        return
    stack = _stack()
    sid = new_id()
    opened.append((name, sid, stack[-1] if stack else None, fun_name))
    stack.append(sid)


def _jax_close(event: str, start: float, end: float, fun_name: str = "",
               **_):
    """Time-span listener: both ends are ``time.time()``'s."""
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    opened = _local.__dict__.get("jax_open")
    if not opened or opened[-1][0] != name:
        # Opened before watch_process(): nothing was kept of its start.
        record(name, start, end, fun=fun_name)
        return
    _, sid, parent, _ = opened.pop()
    if sid is None:
        return
    stack = _stack()
    if sid in stack:
        stack.remove(sid)
    _emit(name, start, end, sid, parent, {"fun": fun_name})


def _compiling() -> Dict[str, str]:
    """``fun=`` of the ``jax.compile`` open on this thread, if one is."""
    for name, sid, _, fun_name in reversed(
            _local.__dict__.get("jax_open", ())):
        if name == "jax.compile" and sid is not None:
            return {"fun": fun_name}
    return {}


def _jax_duration(event: str, duration: float, **_):
    if event == _JAX_CACHE_LOAD:  # the read ends where JAX reports it
        end = time.time()
        record("jax.cache_load", end - duration, end, **_compiling())


def _jax_event(event: str, **_):
    if event == _JAX_CACHE_MISS:  # an event is a span of no length
        now = time.time()
        record("jax.cache_miss", now, now, **_compiling())


# The collection under way: (its TraceAnnotation or None, its start).
# Collections are serialised and both callbacks of one run on the
# collecting thread, so one slot does.
_gc_started: Tuple[Any, float] = (None, 0.0)


def _gc_phase(phase: str, info: Dict[str, int]):
    """``gc.callbacks`` entry.  It may run at any allocation, inside code
    that holds the runtime's locks — ``record_span``'s own among them: so
    it takes none.  A pause stopped every thread: it is a process-wide
    span, stored with the next span this process closes (``_emit``'s
    ``store_later``, the one user)."""
    global _gc_started
    if phase == "start":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        annotation = None
        if profiler is not None:  # on the /host:CPU plane, under a trace
            annotation = profiler.TraceAnnotation(
                "gc.pause", generation=info["generation"])
            annotation.__enter__()
        _gc_started = (annotation, time.time())
        return
    end = time.time()
    annotation, start = _gc_started
    if annotation is not None:
        annotation.__exit__(None, None, None)
    if info["generation"] < 2 and end - start < GC_PAUSE_MIN_S:
        return
    _emit("gc.pause", start, end, new_id(), None,
          {"generation": info["generation"]}, process_wide=True,
          store_later=True)


def _lag_meter():
    """The lag meter's thread: sleep ``LAG_SLEEP_S`` at a time and, woken
    over ``LAG_MIN_S`` late, record the process-wide span ``host.lag``
    from the moment the sleep should have ended to the moment this thread
    ran again.  For that long NO Python thread of the process could be
    scheduled and take the interpreter: the process was descheduled or
    stopped, or a thread held the interpreter's lock through a call that
    does not release it.  (Python threads that only take turns are no
    lag: a waiting thread is handed the lock within
    ``sys.getswitchinterval()``, 5 ms.)  An interval that is over cannot be
    handed to the profiler, so under a trace the lag is a mark on
    ``/host:CPU`` where it ENDS, its length in the stat ``lag_ms``."""
    while True:
        due = time.monotonic() + LAG_SLEEP_S
        time.sleep(LAG_SLEEP_S)
        late = time.monotonic() - due
        if late <= LAG_MIN_S:
            continue
        end = time.time()
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            with profiler.TraceAnnotation("host.lag",
                                          lag_ms=round(1e3 * late, 3)):
                pass
        record("host.lag", end - late, end, process_wide=True)


def watch_process() -> None:
    """From here on this process records, as spans: JAX's compile
    pipeline by function — ``jax.trace``, ``jax.lower``, ``jax.compile``
    (``fun=``; only the outermost of its kind on a thread, and no trace
    that a lowering makes, so the totals add up), ``jax.cache_load``
    inside the ``jax.compile`` that read the persistent cache,
    ``jax.cache_miss`` (no length) — and, process-wide, ``gc.pause``
    (``generation=``) for every collection of generation 2 and any that
    took over ``GC_PAUSE_MIN_S``, and ``host.lag`` (``_lag_meter``, one
    daemon thread).  Idempotent.  For a process that has imported JAX
    already: a worker that was granted chips calls it as it opens them
    (``train/backend.py::bring_up``), any other process that builds train
    steps where ``train/core.py`` is imported; a driver that must stay
    off the chip never comes here."""
    if _gc_phase in gc.callbacks:
        return
    from jax import monitoring

    monitoring.register_scalar_listener(_jax_open)
    monitoring.register_event_time_span_listener(_jax_close)
    monitoring.register_event_duration_secs_listener(_jax_duration)
    monitoring.register_event_listener(_jax_event)
    gc.callbacks.append(_gc_phase)
    threading.Thread(target=_lag_meter, daemon=True,
                     name="ray_tpu-lag").start()


# ------------------------------------------------------- head-side reads --

def get_task_spans(limit: int = 200_000,
                   parents: Optional[Sequence[str]] = None
                   ) -> List[Dict[str, Any]]:
    """Raw spans aggregated at the head; with ``parents``, only those
    caused by one of these span ids."""
    rt = require_runtime()
    filters = {"limit": limit}
    if parents is not None:
        filters["parents"] = list(parents)
    if rt.is_worker():
        reply = rt._request(
            lambda rid: ("state_req", rid, "spans", filters))
        if isinstance(reply, Exception):
            raise reply
        return reply
    return rt.state_query("spans", **filters)


def handler_stats() -> List[Dict[str, Any]]:
    """Per-message-handler latency counters on the head loop
    (reference: src/ray/common/event_stats.h)."""
    rt = require_runtime()
    if rt.is_worker():
        reply = rt._request(
            lambda rid: ("state_req", rid, "handler_stats", {}))
        if isinstance(reply, Exception):
            raise reply
        return reply
    return rt.state_query("handler_stats")


def chrome_trace(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Spans -> Chrome trace-event list ("X" complete events; pid=node,
    tid=worker, so Perfetto lays tasks out per worker lane; the driver
    has a lane of its own)."""
    events: List[Dict[str, Any]] = []
    # Stable short lane ids: Perfetto renders pid/tid as numbers-with-
    # names via metadata events; thread names bind per (pid, tid), so
    # lanes are tracked as (node, worker) pairs.
    node_ids: Dict[str, int] = {}
    lane_ids: Dict[tuple, int] = {}
    for s in spans:
        node = s.get("node_id") or "head"
        pid = node_ids.setdefault(node, len(node_ids) + 1)
        tid = lane_ids.setdefault((node, s["worker_id"]),
                                  len(lane_ids) + 1)
        args = {"task_id": s["task_id"]}
        for k in ("span_id", "parent", "submitted"):
            if s.get(k) is not None:
                args[k] = s[k]
        args.update(s.get("args") or {})
        events.append({
            "name": s["name"],
            "cat": s.get("kind", "task"),
            "ph": "X",
            "ts": round(s["start"] * 1e6, 1),
            "dur": round((s["end"] - s["start"]) * 1e6, 1),
            "pid": pid,
            "tid": tid,
            "args": args,
        })
    for nid, pid in node_ids.items():
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"node {nid[:12]}"}})
    for (node, wid), tid in lane_ids.items():
        lane = wid if wid == "driver" else f"worker {wid[:12]}"
        events.append({"name": "thread_name", "ph": "M",
                       "pid": node_ids[node], "tid": tid,
                       "args": {"name": lane}})
    return events


def timeline(filename: Optional[str] = None):
    """Dump the cluster's task timeline (reference: ``ray.timeline()`` /
    ``ray timeline``).  With ``filename``, writes Chrome trace JSON and
    returns the path; otherwise returns the event list."""
    events = chrome_trace(get_task_spans())
    if filename is None:
        return events
    with open(filename, "w", encoding="utf-8") as f:
        json.dump(events, f)
    return filename


# ------------------------------------------- device trace -> step parts --

PHASES = ("forward", "remat", "backward", "optimizer")
# Row of the ops that belong to the ``lax.scan`` over layers and to no
# layer part: slicing one layer's weights out of the stacked parameters,
# writing its gradients into the stacked gradients, the loop itself.
SCAN = "scan"
# How the ``name=`` of the program's Pallas kernels start (ops/attention.py,
# ops/moe.py, ops/ssm.py, ops/streams.py, ops/delta.py, ops/rotary.py,
# ops/sparse_attention.py; ``selscan_``: ops/ssm.py's selective scan): the
# kernel rows of ``step_breakdown``.  A step scope that starts the same way
# (``hc_map``, ``hc_mix``) is no kernel's name.
KERNEL_NAMES = ("flash_", "moe_gmm", "moe_tgmm", "ssd_", "gated_norm_", "hc_",
                "delta_", "rope_", "kdarule_", "causal_conv_", "sparse_",
                "selscan_")
_SCOPE_TOKENS = re.compile(r"[^/()]+")


def scope_and_phase(op_name: str, scopes: Sequence[str]
                    ) -> Tuple[Optional[str], str]:
    """The step scope and the phase an op's ``op_name`` (its JAX name
    stack) puts it in.  ``jit(step)/jvp(lm_head)/dot_general`` is
    (lm_head, forward); under ``rematted_computation`` the forward pass is
    run again for the backward (remat); any other ``transpose(jvp(..))``
    is the backward pass; the ``optimizer`` scope is a phase of its own."""
    tokens = _SCOPE_TOKENS.findall(op_name)
    scope = next((t for t in tokens if t in scopes), None)
    if scope is None and "while" in tokens:
        scope = SCAN  # the scan's own ops: no scope opens round them
    if scope == "optimizer":
        return scope, "optimizer"
    if "rematted_computation" in op_name:
        return scope, "remat"
    if "transpose(" in op_name:
        return scope, "backward"
    return scope, "forward"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterable[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """plane name -> {event name: op_name}.  The profiler keeps an op's
    JAX name stack as the stat ``tf_op`` of the event's METADATA, which
    ``jax.profiler.ProfileData`` (jaxlib 0.9) does not hand out; so this
    one map is read from the file's protobuf wire format directly
    (``XSpace.planes=1``; ``XPlane.name=2, event_metadata=4,
    stat_metadata=5``; ``XEventMetadata.name=2, stats=5``; ``XStat
    .metadata_id=1, str_value=5``; ``XStatMetadata.name=2``)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(xplane):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f in (4, 5):  # map entry: key=1, value=2
                entry = dict(_fields(v))
                if f == 4:
                    events.append(entry.get(2, b""))
                else:
                    stat_names[entry.get(1, 0)] = dict(
                        _fields(entry.get(2, b""))).get(2, b"").decode()
        names = out.setdefault(name, {})
        for meta in events:
            event_name, op_name = "", None
            for f, v in _fields(meta):
                if f == 2:
                    event_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        op_name = stat[5].decode()
                    elif 7 in stat:  # ref_value: a shared string
                        op_name = stat_names.get(stat[7])
            if op_name:
                names[event_name] = op_name.rstrip(":")
    return out


# The q and k operands of a flash kernel's custom call, as the HLO text
# of its trace event gives them.  Turned round to the kernels' own layout:
# ``dtype[b,h,sq,d]..., dtype[b,h_kv,sk,d]``.  Where the model leaves them
# (a head of whole lane blocks, ``ops/attention.py``): ``dtype[b,sq,h x
# d]..., dtype[b,sk,h_kv x d]``, and the q heads' count is that of the
# call's float32 stats: ``f32[b,h,sq,128]``, a result of ``flash_fwd``, or
# ``f32[b,h,1,sq]``, operands of the one backward kernel (``flash_dkv``,
# whose three results are dq, dk, dv).
_FLASH_OPERANDS = re.compile(
    r"operand_layout_constraints=\{(\w+)\[\d+,\d+,(\d+),(\d+)\]\{[^}]*\}, "
    r"\w+\[\d+,\d+,(\d+),\d+\]")
_FLASH_OPERANDS_IN_PLACE = re.compile(
    r"operand_layout_constraints=\{(\w+)\[\d+,(\d+),(\d+)\]\{[^}]*\}, "
    r"\w+\[\d+,(\d+),\d+\]\{")
_FLASH_STATS = re.compile(r"f32\[\d+,(\d+),\d+,\d+\]")
_HLO_DTYPES = {"bf16": "bfloat16", "f16": "float16", "f32": "float32"}


def flash_executed_over_causal(text: str) -> Optional[float]:
    """(q, k) pairs a flash kernel computes over the pairs the causal
    mask leaves, for the kernel whose custom call has the HLO ``text``:
    ``ops.attention.causal_tile_counts`` at the tile sizes this tree
    picks for the operands' shapes (the step's attention is causal), in
    either of the two forms the kernels take their operands in.
    Static per shape: nothing is counted at run time.  None where the
    text names no such operands."""
    m = _FLASH_OPERANDS.search(text)
    if m is not None:
        dtype, sq, d, sk = m.group(1), *map(int, m.groups()[1:])
    else:
        m = _FLASH_OPERANDS_IN_PLACE.search(text)
        stats = _FLASH_STATS.search(text)
        if m is None or stats is None:
            return None
        dtype, sq, width, sk = m.group(1), *map(int, m.groups()[1:])
        d = width // int(stats.group(1))
    if dtype not in _HLO_DTYPES:
        return None
    from ray_tpu.ops.attention import causal_tile_counts, choose_tiles

    tiles = choose_tiles(sq, sk, True, d, _HLO_DTYPES[dtype])
    if tiles is None:
        return None
    n = causal_tile_counts(sq, sk, *tiles)
    return n["executed_pairs"] / n["causal_pairs"]


def breakdown_planes(planes, names: Dict[str, Dict[str, str]],
                     step_module: str, scopes: Sequence[str]
                     ) -> Optional[Dict[str, Any]]:
    """``step_breakdown`` on loaded planes: ``planes`` maps plane name ->
    line name -> [(event name, start_ns, end_ns)], ``names`` is
    ``op_names``' map.  The chip that is busiest is reported."""
    best = None
    for plane_name, lines in sorted(planes.items()):
        if not plane_name.startswith("/device:TPU:"):
            continue
        steps = sorted(
            (e for e in lines.get("XLA Modules", ())
             if e[0] == step_module or e[0].startswith(step_module + "(")),
            key=lambda e: e[1])
        if len(steps) < 2:
            continue
        # As benchmark/trace_reduce.py: the first execution is a lead-in.
        steps = steps[1:]
        op_name = names.get(plane_name, {})
        by_scope: Dict[str, Dict[str, float]] = {}
        kernels: Dict[str, float] = {}
        kernel_calls: Dict[str, int] = {}
        kernel_pairs: Dict[str, float] = {}
        unscoped: Dict[str, float] = {}
        busy = 0
        ops = sorted(lines.get("XLA Ops", ()), key=lambda e: (e[1], -e[2]))
        open_ops: List[list] = []  # [name, end, self_ns] of enclosing ops

        def close(entry):
            nonlocal busy
            text, _, self_ns = entry
            if self_ns <= 0:
                return
            busy += self_ns
            stack = op_name.get(text, "")
            scope, phase = scope_and_phase(stack, scopes)
            if scope is None:
                label = text.partition(" = ")[0].lstrip("%")
                unscoped[label] = unscoped.get(label, 0) + self_ns
            else:
                row = by_scope.setdefault(scope, {})
                row[phase] = row.get(phase, 0) + self_ns
            if 'custom_call_target="tpu_custom_call"' in text:
                kernel = next((t for t in _SCOPE_TOKENS.findall(stack)
                               if t.startswith(KERNEL_NAMES)
                               and t not in scopes), "unnamed")
                key = kernel + (".remat" if phase == "remat" else "")
                kernels[key] = kernels.get(key, 0) + self_ns
                kernel_calls[key] = kernel_calls.get(key, 0) + 1
                # (a windowed kernel's ratio needs its window, which the
                # text does not hold: the step's metric
                # ``attn_window_executed_share`` has it)
                if (kernel.startswith("flash_") and key not in kernel_pairs
                        and not kernel.endswith("_win")):
                    ratio = flash_executed_over_causal(text)
                    if ratio is not None:
                        kernel_pairs[key] = ratio

        for text, a, b in ops:
            if not any(s[1] <= a < s[2] for s in steps):
                continue
            while open_ops and open_ops[-1][1] <= a:
                close(open_ops.pop())
            if open_ops:  # a while covers its body: self time only
                open_ops[-1][2] -= min(b, open_ops[-1][1]) - a
            open_ops.append([text, b, b - a])
        while open_ops:
            close(open_ops.pop())
        n = len(steps)
        result = {
            "device": int(plane_name.rsplit(":", 1)[1]),
            "steps": n,
            "step_s": sum(e[2] - e[1] for e in steps) / n / 1e9,
            "busy_s": busy / n / 1e9,
            "scopes": {s: {p: t / n / 1e9 for p, t in row.items()}
                       for s, row in by_scope.items()},
            "unscoped_s": sum(unscoped.values()) / n / 1e9,
            "unscoped_ops": sorted(
                ([k, t / n / 1e9] for k, t in unscoped.items()),
                key=lambda kv: -kv[1])[:10],
            "kernels": {k: t / n / 1e9 for k, t in kernels.items()},
            "kernel_calls": {k: c / n for k, c in kernel_calls.items()},
            "kernel_pairs": kernel_pairs,
        }
        if best is None or result["busy_s"] > best["busy_s"]:
            best = result
    return best


def step_breakdown(xplane_path: str, step_module: str = "jit_step",
                   scopes: Optional[Sequence[str]] = None
                   ) -> Optional[Dict[str, Any]]:
    """Device seconds per step of a profiler trace (``.xplane.pb``) by step
    scope and phase: ``{"scopes": {scope: {phase: s}}, "unscoped_s",
    "unscoped_ops", "kernels": {name: s}, "kernel_calls": {name: calls a
    step}, "kernel_pairs": {name: executed / causal pairs}, "step_s",
    "busy_s", "steps", "device"}``.  SELF times
    (a ``while`` covers its body), over the executions of ``step_module``
    after the first; the Mosaic kernels by the ``name=`` of their
    ``pallas_call``.  None when the trace holds no two executions of the
    module.  Command line::

        python -m ray_tpu.scripts step-breakdown <file.xplane.pb>
    """
    from jax.profiler import ProfileData

    if scopes is None:
        from ray_tpu.train.core import STEP_SCOPES as scopes
    with open(xplane_path, "rb") as f:
        raw = f.read()
    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for e in line.events)
    return breakdown_planes(planes, op_names(raw), step_module, scopes)


def format_breakdown(b: Dict[str, Any]) -> str:
    """The table ``PERF.md`` §5 carries: ms per step and share of the
    step's busy time, scope by phase."""
    busy = b["busy_s"] or 1.0
    out = [f"device {b['device']}: {b['steps']} steps, "
           f"step {b['step_s'] * 1e3:.3f} ms, busy {b['busy_s'] * 1e3:.3f} ms",
           f"{'scope':<12}" + "".join(f"{p:>11}" for p in PHASES)
           + f"{'total ms':>11}{'share %':>9}"]
    for scope, row in sorted(b["scopes"].items(),
                             key=lambda kv: -sum(kv[1].values())):
        total = sum(row.values())
        out.append(f"{scope:<12}"
                   + "".join(f"{row.get(p, 0.0) * 1e3:>11.3f}"
                             for p in PHASES)
                   + f"{total * 1e3:>11.3f}{100 * total / busy:>9.2f}")
    out.append(f"{'unscoped':<12}{'':>44}{b['unscoped_s'] * 1e3:>11.3f}"
               f"{100 * b['unscoped_s'] / busy:>9.2f}")
    for name, t in b["unscoped_ops"]:
        out.append(f"  unscoped {t * 1e3:9.3f} ms  {name}")
    for name, t in sorted(b["kernels"].items()):
        pairs = b.get("kernel_pairs", {}).get(name)
        calls = b.get("kernel_calls", {}).get(name)
        out.append(f"  kernel   {t * 1e3:9.3f} ms  {name}" + (
            "" if pairs is None else f"  executed/causal {pairs:.4f}") + (
            "" if calls is None else f"  x {calls:g} a step"))
    return "\n".join(out)
