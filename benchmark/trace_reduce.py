"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.  Part of the yardstick: no change to the program can move it.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU v5e
trace of this repository's train step holds (looked at by hand, PR 22;
``python benchmark/trace_reduce.py <file>`` prints the same overview):

- one plane ``/device:TPU:<n>`` per chip.  Its line ``XLA Modules`` has
  one event per execution of a jitted program, named ``<module>(<id>)``
  (``jit_step(...)`` is the train step).  Its line ``XLA Ops`` has one
  event per executed HLO operation, named by the instruction's WHOLE text
  (``%fusion.379 = (bf16[4096]{...}, ...) fusion(...), kind=kOutput, ...``):
  ``parse_op`` takes the name, the opcode and the result shape out of it.
  A ``while`` (the scan over layers) covers its body's events, so
  durations nest and a time by name must be a SELF time.  The line
  ``Async XLA Ops`` holds copies that run beside the op stream; it is not
  read.  (``Steps`` repeats the modules; ``TC Overlay`` was empty.)
- the Mosaic kernels are the ``custom-call`` ops whose target is
  ``tpu_custom_call``; unnamed as they are today they appear as
  ``closed_call.N`` (flash forward), ``rematted_computation.N`` (the same
  forward, rematerialised) and ``checkpoint.N`` (dKV and dQ).  Other
  custom calls (``AllocateBuffer``, ``ConcatBitcast``) take no time.
- ``/host:CPU`` has one line per host thread; the loop's
  ``TraceAnnotation`` spans are events on the line ``python3``, on the
  same clock as the device lines.

Definitions (one chip; a mesh reports the worst chip where it says so):

- the traced steps are the step module's executions, in order.  The
  first is a lead-in (the device was drained before the trace started)
  and only marks the start: the WINDOW runs from its end to the end of
  the last step, so it holds every measured step and every gap before one.
- busy: the union of the ``XLA Ops`` intervals inside the window.
  idle = window - busy, exactly.
- a gap: a maximal idle interval; labelled with the host annotation that
  overlaps it longest, else ``unannotated``.
- flash: the Mosaic custom calls (these programs have no other
  kernel).  collectives: all-gather, all-reduce,
  reduce-scatter, collective-permute, all-to-all, with their async
  ``-start``/``-done`` halves.  An op line is one stream: whatever time a
  collective event takes there, no compute runs beside it on that chip,
  so its self time IS exposed time.
"""

from __future__ import annotations

import functools
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Event = Tuple[str, int, int]  # name, start_ns, end_ns


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events sorted by start, the longer
    first at equal starts (lines of one name are merged)."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for e in line.events)
    for lines in planes.values():
        for events in lines.values():
            events.sort(key=lambda e: (e[1], -e[2]))
    return planes


@functools.lru_cache(maxsize=None)  # some 600 texts, 40000 events a chip
def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, label) of an ``XLA Ops`` event.  The label — name,
    opcode, result shape without layouts — is what a breakdown shows."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:  # not an instruction's text: opcode from the name
        return name, re.sub(r"[.\d]+$", "", name), name[:120]
    m = _OPCODE.search(rest)
    if not m:
        return name, "", name[:120]
    shape = _LAYOUT.sub("", rest[:m.start() + 1])
    return name, m.group(1), f"{name} {m.group(1)} {shape}"[:120]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        elif b > a:
            out.append((a, b))
    return out


def self_times(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self ns) per event of one line: its duration minus what
    the events nested inside it cover.  ``events`` sorted by start, the
    longer first at equal starts."""
    out: List[List] = []
    stack: List[Tuple[int, int]] = []  # (index in out, end)
    for name, a, b in events:
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(b, stack[-1][1]) - a
        out.append([name, b - a])
        stack.append((len(out) - 1, b))
    return [(n, max(t, 0)) for n, t in out]


def clip(events: Sequence[Event], a: int, b: int) -> List[Event]:
    return [(n, max(s, a), min(e, b)) for n, s, e in events
            if e > a and s < b]


def _label(gap: Tuple[int, int], spans: Sequence[Event]) -> str:
    best, best_ns = "unannotated", 0
    for name, s, e in spans:
        ns = min(e, gap[1]) - max(s, gap[0])
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], *,
                  step_module: str, annotations: Sequence[str]
                  ) -> Optional[Dict]:
    """The reduction; None when the trace has no device plane with at
    least two executions of ``step_module`` (nothing to read)."""
    spans = [e for lines in (planes.get("/host:CPU") or {}).values()
             for e in lines if e[0] in annotations]
    devices = []
    for plane_name, lines in sorted(planes.items()):
        m = DEVICE_PLANE.match(plane_name)
        if not m or OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        steps = [e for e in lines[MODULES_LINE]
                 if e[0] == step_module or e[0].startswith(step_module + "(")]
        if len(steps) < 2:
            continue
        a, b = steps[0][2], steps[-1][2]
        ops = clip(lines[OPS_LINE], a, b)
        busy = union((s, e) for _, s, e in ops)
        busy_ns = sum(e - s for s, e in busy)
        edges = [a] + [t for iv in busy for t in iv] + [b]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by_name: Dict[str, int] = {}
        flash_ns = coll_ns = coll_n = 0
        for text, ns in self_times(ops):
            _, opcode, label = parse_op(text)
            by_name[label] = by_name.get(label, 0) + ns
            if opcode == "custom-call" and MOSAIC_TARGET in text:
                flash_ns += ns
            elif COLLECTIVE.match(opcode):
                coll_ns += ns
                coll_n += not opcode.endswith("-done")
        measured = steps[1:]
        devices.append({
            "device": int(m.group(1)),
            "steps": len(measured),
            "window_s": (b - a) / 1e9,
            "busy_s": busy_ns / 1e9,
            "idle_s": (b - a - busy_ns) / 1e9,
            "step_s": [(e - s) / 1e9 for _, s, e in measured],
            "gap_s": [(measured[i][1] - steps[i][2]) / 1e9
                      for i in range(len(measured))],
            "flash_s": flash_ns / 1e9,
            "collective_s": coll_ns / 1e9,
            "collectives_per_step": coll_n / len(measured),
            "device_ops": [[n, t / 1e9] for n, t in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                          for g in sorted(gaps, key=lambda g: g[0] - g[1])[:5]],
        })
    if not devices:
        return None
    return {"step_module": step_module, "devices": devices,
            "host_spans": {n: sum(1 for e in spans if e[0] == n)
                           for n in annotations}}


def reduce_file(path: str, *, step_module: str,
                annotations: Sequence[str]) -> Optional[Dict]:
    return reduce_planes(load(path), step_module=step_module,
                         annotations=annotations)


def overview(path: str, top: int = 12) -> str:
    """What to look at by hand before trusting the reduction."""
    out = []
    for plane, lines in load(path).items():
        out.append(f"plane {plane!r}")
        for line, events in lines.items():
            total: Dict[str, int] = {}
            for n, s, e in events:
                total[n] = total.get(n, 0) + (e - s)
            span = (events[-1][2] - events[0][1]) / 1e6 if events else 0.0
            out.append(f"  line {line!r}: {len(events)} events, "
                       f"{len(total)} names, spans {span:.3f} ms")
            for n, t in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {t / 1e6:12.3f} ms  {n[:100]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(overview(sys.argv[1]))
