"""Collective-traffic statistics parsed from compiled HLO text.

``cost_analysis()`` gives FLOPs and HBM bytes but not inter-chip
traffic, so the roofline's collective term is derived here: every
``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
``collective-permute`` op is extracted from the HLO and converted into
**wire bytes per participating device** using the standard ring-
algorithm cost model:

    all-gather:          (g-1)/g · result_bytes
    reduce-scatter:      (g-1)   · result_bytes      (= (g-1)/g · operand)
    all-reduce:        2·(g-1)/g · bytes
    all-to-all:          (g-1)/g · bytes
    collective-permute:            bytes             (point-to-point)

with ``g`` the replica-group size parsed from the op's
``replica_groups`` attribute.  Ops inside while/scan bodies execute
once per iteration; HLO text does not annotate trip counts, so counts
here are per-execution of the (already scan-rolled) module — consistent
with ``cost_analysis`` which also reports rolled counts.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*\)|[\w\[\],{}\s/:()]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.M)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SRCDST_RE = re.compile(r"source_target_pairs=\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    result_bytes: Dict[str, int]          # sum of result sizes per op kind
    wire_bytes_per_device: float          # ring-model per-device traffic

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = defaultdict(int)
    result_bytes: Dict[str, int] = defaultdict(int)
    wire = 0.0
    seen_done = set()
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        if "-done(" in line:
            continue  # count async pairs once (at -start)
        nbytes = _shape_bytes(shape_str)
        counts[kind] += 1
        result_bytes[kind] += nbytes

        # replica group size
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            groups = [x for x in gm.group(1).split("}") if x.strip(" ,{")]
            first = groups[0].strip(" ,{") if groups else ""
            g = max(1, len([t for t in first.split(",") if t.strip()]))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                g = int(gi.group(2))
        if kind == "all-gather":
            wire += (g - 1) / max(g, 1) * nbytes
        elif kind == "reduce-scatter":
            wire += (g - 1) * nbytes
        elif kind == "all-reduce":
            wire += 2 * (g - 1) / max(g, 1) * nbytes
        elif kind == "all-to-all":
            wire += (g - 1) / max(g, 1) * nbytes
        elif kind == "collective-permute":
            wire += nbytes
    return CollectiveStats(counts=dict(counts), result_bytes=dict(result_bytes),
                           wire_bytes_per_device=wire)


def reshape_transpose_count(hlo_text: str) -> Tuple[int, int]:
    """Layout-churn indicator for the perf loop."""
    resh = len(re.findall(r"=\s*[\w\[\],{}\s/]+?\s+reshape\(", hlo_text))
    tran = len(re.findall(r"=\s*[\w\[\],{}\s/]+?\s+transpose\(", hlo_text))
    return resh, tran
