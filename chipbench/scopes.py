"""The program's own spans and scopes in a profiler trace.

``chipbench/trace.py`` reads the benchmark's spans and whole programs;
this module reads what the program itself labels:

- host spans named ``slot.*`` (``SlotTrainLoop``'s round phases) and
  ``overlay.*`` (the controller's step, rebuild and commit), written by
  ``repro.obs`` spans through ``jax.profiler.TraceAnnotation``;
- the named scope of each device operation.  A TPU profile keeps it as
  the ``tf_op`` stat of the operation's event metadata (the HLO
  instruction's ``op_name``, e.g. ``jit(counted)/step.optimizer/mul:``),
  which ``jax.profiler.ProfileData`` does not expose; ``metadata_scopes``
  reads it from the ``.xplane.pb`` itself.  XLA names a fusion after its
  root instruction, and its ``op_name`` is the root's, so attributing a
  fusion to a scope is approximate: a fusion that pulls in ops of two
  scopes counts under its root's.

A trace of a program that has no such spans or scopes yields empty
lists, and the metrics that read them read nothing.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench.trace import (CONTAINERS, DEVICE_PLANE, Event, clip, length,
                             op_event)

#: host spans of the program, by prefix
HOST_PREFIXES = ("slot.", "overlay.")
#: the ``tf_op`` stat's name
SCOPE_STAT = "tf_op"


# ---------------------------------------------------------------------------
# The scopes, from the .xplane.pb's event metadata
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint or fixed field, a (start, end) pair for a length-delimited
    one."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def metadata_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """{plane name: {event name: scope}} from a serialized ``XSpace``:
    the ``tf_op`` stat of each event metadata entry of each plane
    (``XSpace.planes`` = 1; ``XPlane`` name = 2, event_metadata = 4,
    stat_metadata = 5; map entries key = 1, value = 2;
    ``XEventMetadata`` name = 2, stats = 5; ``XStatMetadata`` name = 2;
    ``XStat`` metadata_id = 1, str_value = 5, ref_value = 7).  An event
    name that two entries give different scopes maps to ``""``."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(data, *plane):
            if f == 2:
                name = _text(data, v)
            elif f in (4, 5):
                value = next((e for k, e in _fields(data, *v) if k == 2),
                             None)
                if value is None:
                    continue
                if f == 4:
                    events.append(value)
                else:
                    sid, sname = 0, ""
                    for k, e in _fields(data, *value):
                        if k == 1:
                            sid = e
                        elif k == 2:
                            sname = _text(data, e)
                    stat_names[sid] = sname
        scope_id = [k for k, n in stat_names.items() if n == SCOPE_STAT]
        if not scope_id:
            continue
        scopes: Dict[str, str] = {}
        for value in events:
            ev_name, scope = "", None
            for k, e in _fields(data, *value):
                if k == 2:
                    ev_name = _text(data, e)
                elif k == 5:
                    stat = dict(_fields(data, *e))
                    if stat.get(1) != scope_id[0]:
                        continue
                    if 5 in stat:
                        scope = _text(data, stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope is None:
                continue
            if scopes.setdefault(ev_name, scope) != scope:
                scopes[ev_name] = ""
        out[name] = scopes
    return out


# ---------------------------------------------------------------------------
# The program's trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScopedOp:
    """One device operation: its instruction name, opcode, interval and
    named scope (``""`` where it has none)."""
    name: str
    opcode: str
    start: float
    end: float
    scope: str = ""


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Event]              # host spans slot.* and overlay.*
    ops: List[List[ScopedOp]]       # per device, in device order


def load(directory: str) -> ProgramTrace:
    """The program's spans and scoped operations of the newest
    ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    path = max(files, key=os.path.getmtime)
    with open(path, "rb") as f:
        raw = f.read()
    scopes = metadata_scopes(raw)
    data = ProfileData.from_serialized_xspace(raw)
    del raw
    spans: List[Event] = []
    devices: Dict[int, List[ScopedOp]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            by_name = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        op = op_event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                        ops.append(ScopedOp(op.name, op.opcode, op.start,
                                            op.end, by_name.get(e.name, "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(HOST_PREFIXES)]
    return ProgramTrace(spans=spans,
                        ops=[devices[k] for k in sorted(devices)])


def of(ctx, directory: Optional[str] = None) -> ProgramTrace:
    """The program's trace of the run ``ctx`` reads, loaded once per
    context from ``directory`` (by default the harness's trace
    directory, which it builds every ``Context`` from).  Raises where
    the two loaders disagree on the devices or their operations, so a
    reading never pairs one device's scopes with another's intervals."""
    prog = getattr(ctx, "program_trace", None)
    if prog is None:
        if directory is None:
            from chipbench.harness import TRACE_DIR
            directory = TRACE_DIR
        prog = load(directory)
        devices = ctx.trace.devices
        if len(prog.ops) != len(devices) or any(
                [(o.name, o.start, o.end) for o in ops]
                != [(e.name, e.start, e.end) for e in dev.ops]
                for ops, dev in zip(prog.ops, devices)):
            raise ValueError(f"the trace under {directory} is not the "
                             f"one the context holds")
        ctx.program_trace = prog
    return prog


# ---------------------------------------------------------------------------
# What the metric readers use
# ---------------------------------------------------------------------------

def in_scope(scope: str, names: Sequence[str]) -> bool:
    """Whether an ``op_name`` path lies under one of the named scopes
    ``names`` (a whole path component, also inside a transformation's
    parentheses such as ``transpose(jvp(model.ssd))``)."""
    return any(re.search(rf"(?<![\w.]){re.escape(n)}(?![\w.])", scope)
               for n in names)


def span_ns(prog: ProgramTrace, names: Sequence[str], lo: float,
            hi: float) -> float:
    """Summed host time of the spans named ``names`` inside [lo, hi]."""
    return sum(length(clip([(s.start, s.end)], lo, hi))
               for s in prog.spans if s.name in names)


def scoped_ns(ops: Sequence[ScopedOp], names: Sequence[str], lo: float,
              hi: float) -> float:
    """Device time of the operations under the scopes ``names`` inside
    [lo, hi].  A ``while`` and the like hold their body's events and
    are left out."""
    return length(clip([(o.start, o.end) for o in ops
                        if o.opcode not in CONTAINERS
                        and in_scope(o.scope, names)], lo, hi))


def per_device_ms(ctx, fn) -> Optional[float]:
    """``fn(ops, device, lo, hi)`` in nanoseconds — a device's scoped
    operations and its ``chipbench.trace.Device`` — averaged over the
    devices and per round, in milliseconds; None where it is 0."""
    prog = of(ctx)
    if not prog.ops:
        return None
    lo, hi = ctx.window
    per = [fn(ops, dev, lo, hi)
           for ops, dev in zip(prog.ops, ctx.trace.devices)]
    value = sum(per) / len(per) / ctx.rounds / 1e6
    return value or None
