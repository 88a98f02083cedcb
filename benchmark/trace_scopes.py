"""A profiler trace by the program's step scopes and kernel names.  Part
of the yardstick: a COPY of what ``ray_tpu/util/tracing.py`` does for the
operator's ``step-breakdown`` (its ``op_names`` and ``scope_and_phase``;
the self-time walk is ``trace_reduce.self_times``), so that no change to
the program's tool can move a metric.  ``trace_reduce.py`` lays it over
every traced run of every cell, and the readers under ``layer_metrics/``
take their rows through the helpers at the end of this file.

What the trace gives (TPU v5e, jaxlib 0.9; looked at by hand, PR 23): every
event of the line ``XLA Ops`` has, in its METADATA, the stat ``tf_op``: the
op's JAX name stack, e.g. ``jit(step)/transpose(jvp(moe_experts))/moe_gmm``.
``jax.profiler.ProfileData`` does not hand metadata stats out, so that one
map is read from the file's protobuf wire format (``XSpace.planes=1``;
``XPlane.name=2, event_metadata=4, stat_metadata=5``; ``XEventMetadata
.name=2, stats=5``; ``XStat.metadata_id=1, str_value=5, ref_value=7``;
``XStatMetadata.name=2``).

The scope of an op is the first element of its name stack that is one of
``SCOPES`` — the names ``ray_tpu.train.core.STEP_SCOPES`` had when this
file was written — or of the names a configuration file lists under its
optional ``"scopes"``: a model that opens new scopes brings them as data.
A program without one of them (the parent of the PR that adds it) simply
has no time under it.  Ops of the layer scan itself
carry a ``while`` and no scope: row ``scan``.  The phase: scope
``optimizer`` is its own; else a stack holding ``rematted_computation``
is the rematerialised forward, else one holding ``transpose(`` the
backward pass, else forward.  A Mosaic kernel (custom call to
``tpu_custom_call``) is named by the stack element its ``pallas_call``'s
``name=`` left: one that starts with one of ``KERNELS`` or of the
configuration's optional ``"kernels"``.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

SCOPES = ("embed", "attn_qkv", "attention", "attn_out", "ffn", "moe_route",
          "moe_dispatch", "moe_experts", "moe_combine", "lm_head", "loss",
          "optimizer")
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
PHASES = ("forward", "remat", "backward", "optimizer")
KERNELS = ("flash_", "moe_gmm", "moe_tgmm")
SCAN = "scan"
_TOKENS = re.compile(r"[^/()]+")


def scope_and_phase(op_name: str, scopes: Sequence[str] = SCOPES
                    ) -> Tuple[Optional[str], str]:
    tokens = _TOKENS.findall(op_name)
    scope = next((t for t in tokens if t in scopes), None)
    if scope is None and "while" in tokens:
        scope = SCAN
    if scope == "optimizer":
        return scope, "optimizer"
    if "rematted_computation" in op_name:
        return scope, "remat"
    if "transpose(" in op_name:
        return scope, "backward"
    return scope, "forward"


def kernel_name(op_name: str, kernels: Sequence[str] = KERNELS) -> str:
    return next((t for t in _TOKENS.findall(op_name)
                 if t.startswith(tuple(kernels))), "unnamed")


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
    """plane name -> {event name: op_name (the stat ``tf_op``)}."""
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


# ------------------------------------------------ what the readers share --

def device(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The device a reader of scopes or kernels reads: of a traced run
    the chip whose traced steps took longest (every such reader the same
    one, so their shares add up); None where the run has no trace."""
    trace = run["worker"].get("trace")
    if not trace:
        return None
    return max(trace["devices"], key=lambda d: sum(d["step_s"]))


def scope_seconds(device: Dict[str, Any], scopes: Sequence[str],
                  phases: Sequence[str] = PHASES) -> float:
    """Seconds a step of one device's ``scopes`` rows, in ``phases``."""
    return sum(device["scopes"].get(s, {}).get(p, 0.0)
               for s in scopes for p in phases)


def step_share_pct(run: Dict[str, Any], scopes: Optional[Sequence[str]],
                   phases: Sequence[str] = PHASES) -> Optional[float]:
    """Device self time a step under ``scopes`` (None: every scope the
    trace has) in ``phases``, as a share of the traced steps' device time;
    None where the trace has nothing there."""
    d = device(run)
    if d is None:
        return None
    seconds = scope_seconds(d, d["scopes"] if scopes is None else scopes,
                            phases)
    return 100.0 * seconds * d["steps"] / sum(d["step_s"]) or None


def kernel_ms(run: Dict[str, Any], prefix: str) -> Optional[float]:
    """Device milliseconds a step in the Mosaic kernels whose name (with
    ``.remat`` where rematerialised) starts with ``prefix``; None where
    the trace has none."""
    d = device(run)
    if d is None:
        return None
    return 1e3 * sum(t for k, t in d["kernels"].items()
                     if k.startswith(prefix)) or None
