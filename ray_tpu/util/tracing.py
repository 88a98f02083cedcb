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

import contextlib
import itertools
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ray_tpu._private.api_internal import get_runtime, require_runtime

_PROCESS = os.urandom(4).hex()  # span ids are unique across processes
_ids = itertools.count(1)
_local = threading.local()  # .stack: open span ids; .collectors: _Collected


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


class span:
    """``with span("train.backend_start", workers=4): ...``"""

    __slots__ = ("name", "args", "id", "parent", "submitted", "task_id",
                 "kind", "start", "_annotation")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.id = new_id()
        self.parent = self.submitted = self._annotation = None
        self.task_id = b""
        self.kind = "span"

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
        self.start = time.time()
        return self

    def __exit__(self, *exc_info):
        end = time.time()
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
        _stack().pop()
        for got in getattr(_local, "collectors", ()):
            got.add(self.name, self.start, end)
        rt = get_runtime()
        if rt is not None:
            if not self.task_id and rt.is_worker() \
                    and rt.current_task_id is not None:
                self.task_id = rt.current_task_id.binary()
            rt.record_span((self.task_id, self.name, self.start, end,
                            self.kind, self.id, self.parent, self.submitted,
                            self.args or None))
        return False


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
    """Per-name totals of the spans a thread closed while collecting."""

    def __init__(self):
        # Ids of this thread's spans under which a task or an actor was
        # submitted: what add_caused asks the head about.
        self.causes: set = set()
        self.summary: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, start: float, end: float, count: int = 1,
            total_s: Optional[float] = None, max_s: Optional[float] = None):
        dur = end - start
        total_s = dur if total_s is None else total_s
        max_s = dur if max_s is None else max_s
        s = self.summary.get(name)
        if s is None:
            self.summary[name] = {
                "count": count, "total_s": total_s, "max_s": max_s,
                "first_start": start, "last_end": end}
            return
        s["count"] += count
        s["total_s"] += total_s
        s["max_s"] = max(s["max_s"], max_s)
        s["first_start"] = min(s["first_start"], start)
        s["last_end"] = max(s["last_end"], end)

    def merge(self, summary: Optional[Dict[str, Dict[str, float]]]):
        """Fold in another summary (a worker session's)."""
        for name, s in (summary or {}).items():
            self.add(name, s["first_start"], s["last_end"], s["count"],
                     s["total_s"], s["max_s"])

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
    """Summarise every span this thread closes inside the block."""
    got = _Collected()
    active = _local.__dict__.setdefault("collectors", [])
    active.append(got)
    try:
        yield got
    finally:
        active.remove(got)


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
# ops/moe.py, ops/ssm.py): the kernel rows of ``step_breakdown``.
KERNEL_NAMES = ("flash_", "moe_gmm", "moe_tgmm", "ssd_")
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
# of its trace event gives them: ``dtype[b,h,sq,d]..., dtype[b,h,sk,d]``.
_FLASH_OPERANDS = re.compile(
    r"operand_layout_constraints=\{(\w+)\[\d+,\d+,(\d+),(\d+)\]\{[^}]*\}, "
    r"\w+\[\d+,\d+,(\d+),\d+\]")
_HLO_DTYPES = {"bf16": "bfloat16", "f16": "float16", "f32": "float32"}


def flash_executed_over_causal(text: str) -> Optional[float]:
    """(q, k) pairs a flash kernel computes over the pairs the causal
    mask leaves, for the kernel whose custom call has the HLO ``text``:
    ``ops.attention.causal_tile_counts`` at the tile sizes this tree
    picks for the operands' shapes (the step's attention is causal).
    Static per shape: nothing is counted at run time.  None where the
    text names no such operands."""
    m = _FLASH_OPERANDS.search(text)
    if m is None or m.group(1) not in _HLO_DTYPES:
        return None
    from ray_tpu.ops.attention import causal_tile_counts, choose_tiles

    sq, d, sk = int(m.group(2)), int(m.group(3)), int(m.group(4))
    tiles = choose_tiles(sq, sk, True, d, _HLO_DTYPES[m.group(1)])
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
                               if t.startswith(KERNEL_NAMES)), "unnamed")
                key = kernel + (".remat" if phase == "remat" else "")
                kernels[key] = kernels.get(key, 0) + self_ns
                kernel_calls[key] = kernel_calls.get(key, 0) + 1
                if key not in kernel_pairs:
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
