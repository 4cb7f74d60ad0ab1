"""From a profiler trace to numbers: device busy and idle time, collective
time, time inside the harness's own annotations, and the breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: device ops
are the events of the "XLA Ops" line of each ``/device:TPU:<id>`` plane,
host spans are the harness's ``TraceAnnotation``s on the host plane.  The
rest works on :class:`RawTrace` alone, so tests feed it synthetic traces.
Times are nanoseconds on the profiler's one clock.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
DEVICE_OP_LINE = "XLA Ops"
DEVICE_MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter")

#: The harness's annotations; ``WINDOW`` spans the traced window.
WINDOW = "harness.window"
SPANS = (WINDOW, "harness.generate", "solve", "service.submit",
         "service.step_round")


class Ops(NamedTuple):
    names: List[str]
    start: np.ndarray      # int64 ns
    end: np.ndarray        # int64 ns


class RawTrace(NamedTuple):
    ops: Dict[int, Ops]                              # device id -> ops
    spans: Dict[str, List[Tuple[int, int]]]          # name -> intervals
    modules: Dict[int, Ops] = {}                     # device id -> programs


def load(path: str, devices: Sequence[int]) -> RawTrace:
    """Read ``path`` (an ``.xplane.pb``) for the given device ids."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, Ops] = {}
    modules: Dict[int, Ops] = {}
    spans: Dict[str, List[Tuple[int, int]]] = {s: [] for s in SPANS}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            lines = {line.name: line for line in plane.lines}
            for name, into in ((DEVICE_OP_LINE, ops),
                               (DEVICE_MODULE_LINE, modules)):
                if name in lines:
                    into[int(m.group(1))] = _events(lines[name])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        s = int(ev.start_ns)
                        spans[ev.name].append((s, s + int(ev.duration_ns)))
    for v in spans.values():
        v.sort()
    return RawTrace(ops, spans, modules)


def _events(line) -> Ops:
    names, start, dur = [], [], []
    for ev in line.events:
        names.append(ev.name)
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
    s = np.asarray(start, dtype=np.int64)
    return Ops(names, s, s + np.asarray(dur, dtype=np.int64))


def short_name(op: str) -> str:
    """``fusion.12`` from an op's HLO text (``%fusion.12 = u32[...] ...``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """Disjoint sorted intervals covering the union of [start, end)."""
    if len(start) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:], len(s)) - 1
    return s[idx], reach[last]


def covered(s: np.ndarray, e: np.ndarray, lo: int, hi: int) -> int:
    """Length of the disjoint intervals (s, e) inside [lo, hi)."""
    return int(np.maximum(np.minimum(e, hi) - np.maximum(s, lo), 0).sum())


class Summary:
    """Reductions of one trace over its window (mean over devices)."""

    def __init__(self, raw: RawTrace):
        if not raw.spans.get(WINDOW):
            raise ValueError(f"trace has no {WINDOW!r} span")
        if not raw.ops:
            raise ValueError("trace has no device ops")
        self.raw = raw
        self.lo, self.hi = raw.spans[WINDOW][0]
        self.busy = {d: union(o.start, o.end) for d, o in raw.ops.items()}

    @property
    def window_ns(self) -> int:
        return self.hi - self.lo

    def busy_ns(self, lo: Optional[int] = None,
                hi: Optional[int] = None) -> float:
        lo = self.lo if lo is None else max(lo, self.lo)
        hi = self.hi if hi is None else min(hi, self.hi)
        if hi <= lo:
            return 0.0
        return float(np.mean([covered(s, e, lo, hi)
                              for s, e in self.busy.values()]))

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    def collective_ns(self) -> float:
        per_dev = []
        for o in self.raw.ops.values():
            mask = np.fromiter((bool(COLLECTIVE.search(n)) for n in o.names),
                               bool, len(o.names))
            s, e = union(o.start[mask], o.end[mask])
            per_dev.append(covered(s, e, self.lo, self.hi))
        return float(np.mean(per_dev))

    def spans(self, name: str) -> List[Tuple[int, int]]:
        """The annotation's intervals that lie inside the window."""
        return [(s, e) for s, e in self.raw.spans.get(name, ())
                if s >= self.lo and e <= self.hi]

    def first_module_after(self, t: int, pattern: str) -> Optional[int]:
        """Start of the first run of a program whose name matches
        ``pattern`` at or after ``t``, on any of the cell's chips."""
        rx = re.compile(pattern)
        firsts = [int(s) for o in self.raw.modules.values()
                  for name, s in zip(o.names, o.start)
                  if s >= t and rx.search(name)]
        return min(firsts) if firsts else None

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device ops with the most time in the window, in
        seconds per device (summed over their calls)."""
        total: Dict[str, float] = {}
        for o in self.raw.ops.values():
            dur = np.minimum(o.end, self.hi) - np.maximum(o.start, self.lo)
            for name, d in zip(o.names, dur):
                if d > 0:
                    name = short_name(name)
                    total[name] = total.get(name, 0.0) + float(d)
        n_dev = len(self.raw.ops)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n_dev / 1e9] for name, ns in ranked]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps in which no device op ran (on device 0
        of the cell), each named by the host annotation it started in."""
        s, e = self.busy[min(self.busy)]
        inside = (e > self.lo) & (s < self.hi)
        s, e = np.clip(s[inside], self.lo, self.hi), np.clip(
            e[inside], self.lo, self.hi)
        gap_s = np.concatenate([[self.lo], e])
        gap_e = np.concatenate([s, [self.hi]])
        length = gap_e - gap_s
        order = np.argsort(-length, kind="stable")[:k]
        return [[self.host_label(int(gap_s[i])), float(length[i]) / 1e9]
                for i in order if length[i] > 0]

    def host_label(self, t: int) -> str:
        """The innermost harness annotation open at ``t``."""
        best, width = "harness", None
        for name in SPANS:
            if name == WINDOW:
                continue
            for s, e in self.raw.spans.get(name, ()):
                if s <= t < e and (width is None or e - s < width):
                    best, width = name, e - s
        return best
