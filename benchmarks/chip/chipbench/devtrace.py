"""Reduce a ``jax.profiler`` trace to device busy time, per-operation
device time and the host's activity in each idle gap.

Busy time is the union of the intervals in which an operation ran on a
device plane (``/device:TPU:<n>``, its "XLA Ops" line), averaged over the
chips.  The traced window is the span of the benchmark's host annotations
(``trainer.*``, on the trace's own clock); device time outside it is not
counted.  Each idle gap inside the window is named by the annotation that
covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "trainer."


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds, the trace's clock
    dur: float
    module: str = ""


@dataclasses.dataclass
class Profile:
    busy_s: float                              # mean over the chips
    window_s: float
    ops: List[Event]                           # device ops, all chips
    modules: List[Event]
    gaps: List[Tuple[str, float]]              # (host activity, seconds)
    chips: int

    def kernel(self, name: str) -> List[Event]:
        """Device events of one Pallas kernel: the custom calls named after
        it (``%<name>.<n> = ... custom-call(...)``), not the copies and
        pads of the jitted wrapper around it."""
        return [e for e in self.ops
                if e.name.startswith(f"%{name}.") and "custom-call(" in e.name]

    def top_modules(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for e in self.modules or self.ops:
            key = e.name.split("(")[0]
            tot[key] = tot.get(key, 0.0) + e.dur
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps, key=lambda g: -g[1])[:n]]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        stats = dict(e.stats)
        out.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         str(stats.get("hlo_module", ""))))
    return out


def find_xplane(logdir: str) -> Optional[str]:
    """The newest trace the profiler wrote under ``logdir``."""
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def reduce_file(path: str) -> Optional[Profile]:
    """Reduce an ``.xplane.pb`` file (gzipped when its name ends ``.gz``)."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce(ProfileData.from_serialized_xspace(f.read()))


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce(pd) -> Optional[Profile]:
    """None when the trace holds no device plane."""
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not planes:
        return None
    ops: List[Event] = []
    modules: List[Event] = []
    per_chip: List[List[Tuple[float, float]]] = []
    for p in planes:
        lines = {ln.name: ln for ln in p.lines}
        chip_ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        chip_mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        ops += chip_ops
        modules += chip_mods
        per_chip.append(union([(e.start, e.start + e.dur) for e in chip_ops or chip_mods]))
    marks = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                marks += [e for e in _events(ln) if e.name.startswith(HOST_MARK)]
    spans = [iv for chip in per_chip for iv in chip]
    if not spans:
        return None
    if marks:
        lo = min(m.start for m in marks)
        hi = max(m.start + m.dur for m in marks)
        per_chip = [_clip(chip, lo, hi) for chip in per_chip]
    else:
        lo = min(iv[0] for iv in spans)
        hi = max(iv[1] for iv in spans)
    window = hi - lo
    busy = sum(sum(b - a for a, b in chip) for chip in per_chip) / len(per_chip)
    gaps = []
    for chip in per_chip[:1]:
        edges = [(lo, lo)] + chip + [(hi, hi)]
        for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            who = [m.name for m in marks if m.start <= mid <= m.start + m.dur]
            gaps.append((who[0] if who else "no host mark", b - a))
    return Profile(busy, window, ops, modules, gaps, len(planes))
