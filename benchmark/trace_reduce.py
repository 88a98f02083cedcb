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
  ``tpu_custom_call``.  Each is named by what its ``pallas_call``'s
  ``name=`` left on the op's JAX name stack (``trace_scopes.py``; in a
  trace from before PR 23 they are ``unnamed``).  Other custom calls
  (``AllocateBuffer``, ``ConcatBitcast``) take no time.
- ``/host:CPU`` has one line per host thread; the loop's
  ``TraceAnnotation`` spans are events on the line ``python3``, on the
  same clock as the device lines, and so are the program's own spans
  (``ray_tpu.util.tracing.span``) that opened while the profiler ran.

Definitions (one chip; a mesh reports the worst chip where it says so):

- the traced steps are the step module's executions, in order.  The
  first is a lead-in (the device was drained before the trace started)
  and only marks the start: the WINDOW runs from its end to the end of
  the last step, so it holds every measured step and every gap before one.
- busy: the union of the ``XLA Ops`` intervals inside the window.
  idle = window - busy, exactly.
- a gap: a maximal idle interval; labelled with the shortest of the
  program's spans that covers it, then ``/`` and the loop's annotation
  that overlaps it longest (either alone where there is only one), else
  ``unannotated``.
- every op has a scope and a phase (``trace_scopes.py``): ``scopes``,
  ``kernels`` and ``unscoped_s`` are device seconds A STEP by them, and a
  ``device_ops`` label starts ``<scope>/<phase>``.
- kernels: the Mosaic custom calls (``kernels_s``); flash: those whose
  kernel name starts ``flash_`` (``flash_s``).  Both over the whole
  window.  collectives: all-gather, all-reduce,
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

from benchmark import trace_scopes

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


def _label(gap: Tuple[int, int], annotated: Sequence[Event],
           spans: Sequence[Event]) -> str:
    covering = [(e - s, name) for name, s, e in spans
                if s <= gap[0] and gap[1] <= e]
    best, best_ns = "", 0
    for name, s, e in annotated:
        ns = min(e, gap[1]) - max(s, gap[0])
        if ns > best_ns:
            best, best_ns = name, ns
    parts = ([min(covering)[1]] if covering else []) + ([best] if best else [])
    return "/".join(parts) or "unannotated"


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], *,
                  step_module: str, annotations: Sequence[str],
                  spans: Sequence[str] = (),
                  names: Optional[Dict[str, Dict[str, str]]] = None,
                  scopes: Sequence[str] = (), kernels: Sequence[str] = ()
                  ) -> Optional[Dict]:
    """The reduction; None when the trace has no device plane with at
    least two executions of ``step_module`` (nothing to read).

    ``annotations`` are the loop's own ``TraceAnnotation`` names and
    ``spans`` the program's span names, both looked for on the host's
    lines.  ``names`` is ``trace_scopes.op_names`` of the same file
    (without it every op is ``unscoped``); ``scopes`` and ``kernels`` are
    what the configuration adds to ``trace_scopes``' own tuples."""
    host = [e for lines in (planes.get("/host:CPU") or {}).values()
            for e in lines]
    annotated = [e for e in host if e[0] in annotations]
    program = [e for e in host if e[0] in spans]
    scopes = trace_scopes.SCOPES + tuple(scopes)
    kernels = trace_scopes.KERNELS + tuple(kernels)
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
        op_name = (names or {}).get(plane_name, {})
        placed: Dict[str, Tuple] = {}  # an op text, parsed and placed once
        by_name: Dict[str, int] = {}
        by_scope: Dict[str, Dict[str, int]] = {}
        by_kernel: Dict[str, int] = {}
        unscoped_ns = coll_ns = coll_n = 0
        for text, ns in self_times(ops):
            if text not in placed:
                _, opcode, label = parse_op(text)
                stack = op_name.get(text, "")
                scope, phase = trace_scopes.scope_and_phase(stack, scopes)
                kernel = None
                if opcode == "custom-call" and MOSAIC_TARGET in text:
                    kernel = trace_scopes.kernel_name(stack, kernels) + (
                        ".remat" if phase == "remat" else "")
                placed[text] = (
                    scope, phase, opcode, kernel,
                    f"{scope or 'unscoped'}/{phase} {label}"[:120])
            scope, phase, opcode, kernel, label = placed[text]
            by_name[label] = by_name.get(label, 0) + ns
            if scope is None:
                unscoped_ns += ns
            else:
                row = by_scope.setdefault(scope, {})
                row[phase] = row.get(phase, 0) + ns
            if kernel is not None:
                by_kernel[kernel] = by_kernel.get(kernel, 0) + ns
            elif COLLECTIVE.match(opcode):
                coll_ns += ns
                coll_n += not opcode.endswith("-done")
        measured = steps[1:]
        per_step = 1e9 * len(measured)
        devices.append({
            "device": int(m.group(1)),
            "steps": len(measured),
            "window_s": (b - a) / 1e9,
            "busy_s": busy_ns / 1e9,
            "idle_s": (b - a - busy_ns) / 1e9,
            "step_s": [(e - s) / 1e9 for _, s, e in measured],
            "gap_s": [(measured[i][1] - steps[i][2]) / 1e9
                      for i in range(len(measured))],
            "kernels_s": sum(by_kernel.values()) / 1e9,
            "flash_s": sum(t for k, t in by_kernel.items()
                           if k.startswith("flash_")) / 1e9,
            "scopes": {scope: {p: t / per_step for p, t in row.items()}
                       for scope, row in by_scope.items()},
            "kernels": {k: t / per_step for k, t in by_kernel.items()},
            "unscoped_s": unscoped_ns / per_step,
            "collective_s": coll_ns / 1e9,
            "collectives_per_step": coll_n / len(measured),
            "device_ops": [[n, t / 1e9] for n, t in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[_label(g, annotated, program), (g[1] - g[0]) / 1e9]
                          for g in sorted(gaps, key=lambda g: g[0] - g[1])[:5]],
        })
    if not devices:
        return None
    return {"step_module": step_module, "devices": devices,
            "host_spans": {n: sum(1 for e in annotated if e[0] == n)
                           for n in annotations}}


def reduce_file(path: str, *, step_module: str, annotations: Sequence[str],
                spans: Sequence[str] = (), scopes: Sequence[str] = (),
                kernels: Sequence[str] = ()) -> Optional[Dict]:
    with open(path, "rb") as f:
        names = trace_scopes.op_names(f.read())
    return reduce_planes(load(path), step_module=step_module,
                         annotations=annotations, spans=spans, names=names,
                         scopes=scopes, kernels=kernels)


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
