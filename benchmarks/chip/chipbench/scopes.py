"""Device time of the operations a ``jax.named_scope`` of the program put in.

The program wraps a layer in a named scope (``moe``, ``mla``); XLA keeps the
scope in each instruction's metadata (its ``op_name``, e.g.
``jit(_train_launch)/while/body/moe/dot_general``, with transforms wrapped
around it in the backward pass: ``transpose(jvp(moe))``). On a TPU the
profiler's operation events (the ``XLA Ops`` line of each ``/device:``
plane) carry only the instruction's name; the ``op_name`` is in the HLO of
the program the event ran in, which the profiler stores in the trace's
``/host:metadata`` plane (one ``Hlo Proto`` per program, under the name the
``XLA Modules`` line gives the program's runs). This module reads both from
the newest ``.xplane.pb`` of the traced window (the file
:mod:`chipbench.trace` and :mod:`chipbench.program_spans` read): each
operation's program is the one running on its device at its start (programs
run one at a time on a device), its ``op_name`` that program's instruction
of the event's name. It clips the operations to the window and sums, per
device, the time covered by those whose ``op_name`` names a scope (the
union of their intervals), optionally only inside programs whose name holds
a needle.

A program that writes no such scope reduces to ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re

from . import program_spans, trace

_WRAPPER = re.compile(r"^(?:[A-Za-z_]+\()+|\)+$")
METADATA_PLANE = "/host:metadata"
# The grouped products' instructions (``ragged-dot-*``): the TPU's lowering
# of ``lax.ragged_dot`` leaves them without the ``op_name``; in the program
# only the expert layer issues them.
GROUPED_PRODUCTS = ("ragged-dot",)


def names_scope(value: str, scope: str) -> bool:
    """Whether an ``op_name`` path holds ``scope`` as one of its parts, a
    transform's wrappers (``transpose(jvp(...))``) taken off."""
    return any(_WRAPPER.sub("", part) == scope for part in value.split("/"))


# ------------------------------------------------- protobuf wire format
# The XSpace (tsl/profiler/protobuf/xplane.proto) and HloProto
# (xla/service/hlo.proto) fields read here, by number:
#   XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map: key 1, value 2);
#   XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
#   HloProto.hlo_module 1; HloModuleProto.computations 3;
#   HloComputationProto.instructions 2; HloInstructionProto.name 1,
#   .metadata 7; OpMetadata.op_name 2.


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, number: int) -> list:
    """The values of field ``number`` of one message: ints for varints and
    fixed widths, memoryviews for length-delimited fields."""
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        if key >> 3 == number:
            out.append(v)
    return out


def _field(buf, number: int, default=None):
    found = _fields(buf, number)
    return found[-1] if found else default


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", errors="replace") if buf is not None else ""


def _op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of a
    serialized ``HloProto``."""
    names = {}
    module = _field(hlo_proto, 1)
    for comp in _fields(module, 3) if module is not None else []:
        for ins in _fields(comp, 2):
            meta = _field(ins, 7)
            names[_text(_field(ins, 1))] = _text(_field(meta, 2)) if meta is not None else ""
    return names


def op_names(path: str) -> dict[str, dict[str, str]]:
    """Program name (as the ``XLA Modules`` line names its runs) ->
    instruction name -> ``op_name``, from the trace's metadata plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _fields(space, 1):
        if _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        for entry in _fields(plane, 4):
            meta = _field(entry, 2)
            if meta is None:
                continue
            for stat in _fields(meta, 5):
                blob = _field(stat, 6)
                if blob is not None:
                    out[_text(_field(meta, 2))] = _op_names(blob)
    return out


# --------------------------------------------------------- operations
@dataclasses.dataclass(frozen=True)
class Op:
    plane: str
    start_ns: float
    end_ns: float
    name: str  # the HLO instruction's


def instruction(event_name: str) -> str:
    """The HLO instruction an operation event names: the TPU's events give
    the instruction's text, ``%fusion.12 = f32[...] fusion(...)``."""
    head = event_name.split(" = ", 1)[0] if " = " in event_name else event_name
    return head.lstrip("%")


def load_ops(path: str) -> tuple[list[Op], dict]:
    """Operation events of every device plane and each plane's program
    intervals ``[(start, end, name)]``."""
    from jax.profiler import ProfileData

    ops, modules = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops.extend(Op(plane.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                              instruction(e.name)) for e in line.events)
            elif line.name == trace.MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name) for e in line.events)
    for v in modules.values():
        v.sort()
    return ops, modules


def _module_at(modules: list, t: float) -> str | None:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return None


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int):
    """The window's operations, each with its program and ``op_name``."""
    trace_dir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path))))
    t0, t1 = trace.window_of(trace.load_events(trace_dir))
    ops, modules = load_ops(path)
    names = op_names(path)
    out = []
    for o in ops:
        if o.end_ns <= t0 or o.start_ns >= t1:
            continue
        module = _module_at(modules.get(o.plane, []), o.start_ns) or ""
        out.append((o, module, names.get(module, {}).get(o.name, "")))
    return out, t0, t1


def scope_seconds(run, scope: str, module: str | None = None, also: tuple = ()) -> float | None:
    """Device seconds in the traced window of the operations whose
    ``op_name`` names ``scope``, or whose instruction name starts with one
    of ``also`` (instructions a compiler pass left without their metadata),
    summed over devices, only inside programs whose name holds ``module``
    where given; None for an untraced run or where no such operation ran."""
    if run.trace is None:
        return None
    path = program_spans.newest(program_spans.TRACE_DIR)
    if path is None:
        return None
    ops, t0, t1 = _read(path, os.stat(path).st_mtime_ns)
    spans: dict[str, list] = {}
    for o, prog, op_name in ops:
        named = names_scope(op_name, scope) or o.name.startswith(also)
        if named and (module is None or module in prog):
            spans.setdefault(o.plane, []).append((max(o.start_ns, t0), min(o.end_ns, t1)))
    if not spans:
        return None
    return sum(_union_ns(v) for v in spans.values()) / 1e9


def _union_ns(intervals: list) -> float:
    """Length of the union of ``(start, end)`` intervals: an operation
    nested in another, or overlapping it, counts once."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total
