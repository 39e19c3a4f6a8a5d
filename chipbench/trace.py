"""Reduction of a profiler trace to per-layer quantities.

The trace is the JAX profiler's ``.xplane.pb``.  Device planes
(``/device:TPU:<n>``) carry an ``XLA Modules`` line (one event per
program execution, named after the jitted function, e.g.
``jit_counted(<fingerprint>)``), an ``XLA Ops`` line (one event per HLO
operation, named by the instruction's text, ``%name = <shape>
opcode(...)``; a ``while`` holds the events of its body) and an ``Async
XLA Ops`` line (the spans of asynchronous copies and collectives from
start to done).  The host plane carries the
benchmark's own spans (``chipbench.window``, ``chipbench.round``,
``chipbench.make_batch``, ``chipbench.make_params``).  Times are in
nanoseconds on the profiler's common clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
#: operations whose events hold other operations' events
CONTAINERS = ("while", "conditional", "call")


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` that no interval of ``cover`` covers."""
    out: List[Interval] = []
    cover = merge(cover)
    for a, b in merge(intervals):
        cur = a
        for c, d in cover:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle intervals of [lo, hi] between ``intervals``."""
    return subtract([(lo, hi)], intervals)


# ---------------------------------------------------------------------------
# The trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    opcode: str = ""


def op_event(text: str, start: float, end: float) -> Event:
    """An ``XLA Ops`` event: the instruction's name and opcode."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    return Event(name.lstrip("%"), start, end, m.group(1) if m else "")


@dataclasses.dataclass
class Device:
    modules: List[Event]
    ops: List[Event]
    async_ops: List[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Event]            # the benchmark's host spans

    def window(self) -> Interval:
        w = [s for s in self.spans if s.name == "chipbench.window"]
        if not w:
            raise ValueError("the trace holds no chipbench.window span")
        return w[0].start, w[0].end


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[int, Device] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device([], []))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules += [Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns)
                                    for e in line.events]
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    target = (dev.ops if line.name == "XLA Ops"
                              else dev.async_ops)
                    target += [op_event(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    return Trace([devices[k] for k in sorted(devices)], spans)


# ---------------------------------------------------------------------------
# What the metric readers use
# ---------------------------------------------------------------------------

def in_window(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [e for e in events if e.end > lo and e.start < hi]


def program_ns(device: Device, prefix: str, lo: float, hi: float) -> float:
    """Device time of the executions of one program inside [lo, hi]."""
    return length(clip([(e.start, e.end) for e in device.modules
                        if e.name.startswith(prefix)], lo, hi))


def is_collective(e: Event) -> bool:
    return bool(COLLECTIVE.match(e.opcode) or COLLECTIVE.match(e.name))


def exposed_collective_ns(device: Device, prefix: str, lo: float,
                          hi: float) -> float:
    """Time of the collectives (synchronous ones and the start-to-done
    spans of asynchronous ones) inside one program's executions that no
    other operation of the device overlaps."""
    runs = [(e.start, e.end) for e in device.modules
            if e.name.startswith(prefix)]
    ops_in = in_window(device.ops, lo, hi)
    coll = [(e.start, e.end) for e in ops_in + in_window(
        device.async_ops, lo, hi) if is_collective(e)]
    compute = [(e.start, e.end) for e in ops_in
               if not is_collective(e) and e.opcode not in CONTAINERS
               and not e.opcode.endswith(("-start", "-done"))]
    inside = []
    for a, b in merge(runs):
        inside += clip(coll, a, b)
    return length(subtract(inside, compute))


def busy_ns(device: Device, lo: float, hi: float) -> float:
    return length(clip([(e.start, e.end) for e in device.ops], lo, hi))


def kernel_ns(device: Device, needle: str, lo: float, hi: float) -> float:
    """Device time of the operations whose instruction name holds
    ``needle`` (a named scope such as ``kernels.gather_mix``)."""
    return sum(min(e.end, hi) - max(e.start, lo)
               for e in in_window(device.ops, lo, hi) if needle in e.name)


def gather_mix_least_s(params, peaks: dict) -> Tuple[float, str]:
    """Least time of one flat mixing round and what bounds it: one
    ``gather_mix`` call per parameter dtype over the (C, N) buffer,
    reading and writing it once (2*C*N*itemsize bytes) plus the (C, C)
    f32 round matrix, and 2*C*C*N flops."""
    import jax
    import numpy as np
    by_dtype: Dict[object, int] = {}
    C = 0
    for leaf in jax.tree.leaves(params):
        C = leaf.shape[0]
        by_dtype[leaf.dtype] = by_dtype.get(leaf.dtype, 0) + int(
            np.prod(leaf.shape[1:]))
    nbytes = sum(2 * C * n * np.dtype(dt).itemsize + 4 * C * C
                 for dt, n in by_dtype.items())
    flops = sum(2 * C * C * n for n in by_dtype.values())
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")


@dataclasses.dataclass
class Context:
    """Everything a metric reader may read."""
    trace: Trace
    cell: object
    rounds: int
    tokens_per_s: float
    peaks: dict
    step_module: str
    mix_module: str
    family: object
    gather_mix_least_s: float
    gather_mix_bound: str
    units: Dict[str, str]

    @classmethod
    def build(cls, directory: str, *, cell, rounds, window_s, tokens_per_s,
              peaks, step_module, mix_module, family, params):
        least, bound = gather_mix_least_s(params, peaks)
        return cls(trace=load(directory), cell=cell, rounds=rounds,
                   tokens_per_s=tokens_per_s, peaks=peaks,
                   step_module=step_module, mix_module=mix_module,
                   family=family, gather_mix_least_s=least,
                   gather_mix_bound=bound, units=cell.units)

    def unit(self, name: str) -> str:
        return self.units[name]

    @property
    def window(self) -> Interval:
        return self.trace.window()

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        devs = self.trace.devices
        return sum(busy_ns(d, lo, hi) for d in devs) / len(devs) / 1e9

    def per_device(self, fn) -> List[float]:
        lo, hi = self.window
        return [fn(d, lo, hi) for d in self.trace.devices]

    def breakdown(self, top: int = 10) -> dict:
        lo, hi = self.window
        totals: Dict[str, float] = {}
        for d in self.trace.devices:
            for e in in_window(d.ops, lo, hi):
                if e.opcode in CONTAINERS:
                    continue
                key = f"{e.name} ({e.opcode})"
                totals[key] = totals.get(key, 0.0) + (
                    min(e.end, hi) - max(e.start, lo)) / 1e9
        n = len(self.trace.devices)
        ops = sorted(((k, v / n) for k, v in totals.items()),
                     key=lambda kv: -kv[1])[:top]
        d0 = self.trace.devices[0]
        idle = gaps([(e.start, e.end) for e in d0.ops], lo, hi)
        idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        labelled = [[self._label(a, b), (b - a) / 1e9] for a, b in idle]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": labelled}

    def _label(self, a: float, b: float) -> str:
        """The innermost benchmark span around the gap's middle."""
        mid = (a + b) / 2
        around = [s for s in self.trace.spans
                  if s.start <= mid <= s.end and s.name != "chipbench.window"]
        if not around:
            return "outside any span"
        return min(around, key=lambda s: s.end - s.start).name


def nonempty(value: Optional[float]) -> Optional[float]:
    """A reading only where the trace held something to read."""
    return value if value else None
