"""The program's own names in a profiler trace: its host spans and the
scopes its device ops carry.

``repro.obs.spans`` writes two kinds of name into the trace that
``jax.profiler`` records.  Host spans (``repro.solve.round`` ...) are
events on the host plane whose name starts with ``repro.``.  Device scopes
(``engine.evaluate`` ...) are components of each device op's ``op_name``
path (``jit(round_fn)/while/body/engine.evaluate/...``).  ``load`` reads
both from the same ``.xplane.pb`` that ``trace_reduce.load`` reads;
:class:`Program` reduces them over the window that ``trace_reduce.Summary``
holds.  A trace of a program without these names (an older program) has no
program spans and no scoped op; the readers then read nothing.

Device ops nest on their line: a ``while`` op covers the ops of its body.
A scope's time is the *self* time of its ops: an op's duration less the
nested ops it covers, so the self times of all ops add up to the busy time.
The profiler gives a copy that the compiler put into a loop the
``op_name`` of the loop; a loop op itself has none, so its own time (its
condition, its carry) counts as unscoped.

Per-layer readers get the trace through :func:`of`, which loads it once
per run and keeps it on the reader's view.
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import trace_reduce as tr

PROGRAM_SPAN = "repro."
#: A scope is an ``op_name`` component ``engine.*``, ``steal.*`` or
#: ``round.*``, maybe wrapped by a transform (``vmap(engine.select)``).
SCOPE = re.compile(r"^(?:[\w-]+\()*((?:engine|steal|round)\.\w+)\)*$")
#: The stat of a device op's event metadata that holds its ``op_name``
#: (``jit(round_fn)/while/body/engine.evaluate/vmap()/gather:gather``).
OP_NAME_STAT = "tf_op"
ROUND_PROGRAM = r"round_fn"


class ScopedOps(NamedTuple):
    names: List[str]
    start: np.ndarray      # int64 ns
    end: np.ndarray        # int64 ns
    scope: List[str]       # innermost scope of each op, "" for none


class ProgramTrace(NamedTuple):
    spans: Dict[str, List[Tuple[int, int]]]    # ``repro.*`` host spans
    ops: Dict[int, ScopedOps]                  # device id -> ops


def scope_of(op_name: str) -> str:
    """The innermost scope in an ``op_name`` path ("" if none)."""
    for part in reversed(op_name.split(":", 1)[0].split("/")):
        m = SCOPE.match(part)
        if m:
            return m.group(1)
    return ""


# -- the op_name of each device op -------------------------------------------
#
# A TPU trace keeps an op's ``op_name`` in the ``tf_op`` stat of the op's
# event metadata, which ``jax.profiler.ProfileData`` does not expose (its
# events carry their own stats only).  So the ``.xplane.pb`` is read here
# as what it is, an ``XSpace`` protobuf (tsl/profiler/protobuf/xplane.proto),
# for the planes' event and stat metadata alone; the event lines are
# skipped by their length.

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, a (start, end) span for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield number, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(data: bytes, planes: Sequence[str]) -> Dict[str, str]:
    """Event name -> ``op_name`` of the events of the named planes, from
    an ``XSpace``'s serialized bytes (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.id = 1, .name = 2; map entries key = 1, value = 2)."""
    buf = memoryview(data)
    out: Dict[str, str] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for field, value in _fields(buf, *plane):
            if field == 2:
                name = _text(buf, value)
            elif field in (4, 5):
                entry = dict(_fields(buf, *value))
                if 2 not in entry:
                    continue
                if field == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[meta.get(1, entry.get(1))] = \
                        _text(buf, meta[2]) if 2 in meta else ""
        if name not in planes:
            continue
        for span in events:
            event_name, op_name = None, None
            for field, value in _fields(buf, *span):
                if field == 2:
                    event_name = _text(buf, value)
                elif field == 5:
                    stat = dict(_fields(buf, *value))
                    if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                        continue
                    if 5 in stat:
                        op_name = _text(buf, stat[5])
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7], "")
            if event_name is not None and op_name:
                out[event_name] = op_name
    return out


def load(path: str, devices: Sequence[int]) -> ProgramTrace:
    """Read ``path`` (an ``.xplane.pb``) for the given device ids."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    names = op_names(raw, [f"/device:TPU:{d}" for d in devices])
    data = ProfileData.from_serialized_xspace(raw)
    spans: Dict[str, List[Tuple[int, int]]] = {}
    ops: Dict[int, ScopedOps] = {}
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            for line in plane.lines:
                if line.name == tr.DEVICE_OP_LINE:
                    ops[int(m.group(1))] = _scoped(line, names)
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN):
                        s = int(ev.start_ns)
                        spans.setdefault(ev.name, []).append(
                            (s, s + int(ev.duration_ns)))
    for v in spans.values():
        v.sort()
    return ProgramTrace(spans, ops)


def _scoped(line, op_name: Dict[str, str]) -> ScopedOps:
    names, start, dur, scopes = [], [], [], []
    known: Dict[str, str] = {}
    for ev in line.events:
        name = ev.name
        if name not in known:
            known[name] = scope_of(op_name.get(name, ""))
        names.append(name)
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
        scopes.append(known[name])
    s = np.asarray(start, dtype=np.int64)
    return ScopedOps(names, s, s + np.asarray(dur, dtype=np.int64), scopes)


def self_segments(start: np.ndarray, end: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Disjoint segments ``(s, e, op)``: the parts of each op's interval
    that no op nested in it covers.  Together they cover the union of all
    the ops."""
    order = np.lexsort((-end, start))
    seg_s: List[int] = []
    seg_e: List[int] = []
    seg_op: List[int] = []

    def emit(lo, hi, op):
        if hi > lo:
            seg_s.append(lo)
            seg_e.append(hi)
            seg_op.append(op)

    stack: List[List[int]] = []          # [op, cursor]
    for i in order.tolist():
        s = int(start[i])
        while stack and int(end[stack[-1][0]]) <= s:
            j, cur = stack.pop()
            emit(cur, int(end[j]), j)
            if stack:
                stack[-1][1] = max(stack[-1][1], int(end[j]))
        if stack:
            j, cur = stack[-1]
            emit(cur, s, j)
        stack.append([i, s])
    while stack:
        j, cur = stack.pop()
        emit(cur, int(end[j]), j)
        if stack:
            stack[-1][1] = max(stack[-1][1], int(end[j]))
    return (np.asarray(seg_s, np.int64), np.asarray(seg_e, np.int64),
            np.asarray(seg_op, np.int64))


class Program:
    """Reductions of the program's names over a ``Summary``'s window
    (device times are means over the cell's chips)."""

    def __init__(self, summary: tr.Summary, raw: ProgramTrace):
        self.summary, self.raw = summary, raw
        self.lo, self.hi = summary.lo, summary.hi
        self.scopes = sorted({x for o in raw.ops.values() for x in o.scope}
                             | {""})
        ids = {x: i for i, x in enumerate(self.scopes)}
        # device id -> self segments (s, e, op) and each segment's scope id
        self._segments = {}
        for d, o in raw.ops.items():
            seg_s, seg_e, op = self_segments(o.start, o.end)
            scope = np.fromiter((ids[x] for x in o.scope), np.int64,
                                len(o.scope))
            self._segments[d] = (seg_s, seg_e, op, scope[op])

    @property
    def has_spans(self) -> bool:
        return bool(self.raw.spans)

    @property
    def has_scopes(self) -> bool:
        return any(any(o.scope) for o in self.raw.ops.values())

    def spans(self, name: str) -> List[Tuple[int, int]]:
        """The program span's intervals that lie inside the window."""
        return [(s, e) for s, e in self.raw.spans.get(name, ())
                if s >= self.lo and e <= self.hi]

    def inside(self, name: str, lo: int, hi: int) -> List[Tuple[int, int]]:
        """The program span's intervals inside [lo, hi)."""
        return [(s, e) for s, e in self.raw.spans.get(name, ())
                if s >= lo and e <= hi]

    def _self_ns(self, names, lo: Optional[int], hi: Optional[int]) -> float:
        lo = self.lo if lo is None else max(lo, self.lo)
        hi = self.hi if hi is None else min(hi, self.hi)
        if hi <= lo or not self._segments:
            return 0.0
        ids = [i for i, x in enumerate(self.scopes) if x in names]
        return float(np.mean([
            tr.covered(s[keep], e[keep], lo, hi)
            for s, e, _, scope in self._segments.values()
            for keep in [np.isin(scope, ids)]]))

    def scope_ns(self, names: Sequence[str], lo: Optional[int] = None,
                 hi: Optional[int] = None) -> float:
        """Self time of the ops whose innermost scope is one of
        ``names``, inside [lo, hi) and the window."""
        return self._self_ns(set(names), lo, hi)

    def unscoped_ns(self, lo: Optional[int] = None,
                    hi: Optional[int] = None) -> float:
        """Self time of the ops that carry no scope, inside [lo, hi) and
        the window."""
        return self._self_ns({""}, lo, hi)

    def unscoped_in_program_ns(self, pattern: str = ROUND_PROGRAM) -> float:
        """Unscoped self time inside the runs of the programs whose name
        matches ``pattern`` (``round_fn``), in the window."""
        rx = re.compile(pattern)
        runs = sorted({(int(s), int(e))
                       for o in self.summary.raw.modules.values()
                       for name, s, e in zip(o.names, o.start, o.end)
                       if rx.search(name)})
        return sum(self.unscoped_ns(s, e) for s, e in runs)

    def top_self_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device ops with the most self time in the window, in
        seconds per device, each with its scope."""
        total: Dict[Tuple[str, str], float] = {}
        for d, (s, e, op, _) in self._segments.items():
            o = self.raw.ops[d]
            length = np.minimum(e, self.hi) - np.maximum(s, self.lo)
            for i, ns in zip(op.tolist(), length.tolist()):
                if ns > 0:
                    key = (tr.short_name(o.names[i]), o.scope[i])
                    total[key] = total.get(key, 0.0) + ns
        n_dev = max(len(self.raw.ops), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, scope, ns / n_dev / 1e9]
                for (name, scope), ns in ranked]

    def host_label(self, t: int) -> str:
        """The innermost span open at ``t``, among the harness's
        annotations and the program's spans."""
        best, width = "harness", None
        named = [(n, self.summary.raw.spans.get(n, ())) for n in tr.SPANS
                 if n != tr.WINDOW] + list(self.raw.spans.items())
        for name, intervals in named:
            for s, e in intervals:
                if s <= t < e and (width is None or e - s < width):
                    best, width = name, e - s
        return best

    def idle_gaps(self, k: int = 10) -> List[List]:
        """``Summary.idle_gaps``, each gap named by :meth:`host_label`."""
        gaps = []
        s, e = self.summary.busy[min(self.summary.busy)]
        inside = (e > self.lo) & (s < self.hi)
        s = np.clip(s[inside], self.lo, self.hi)
        e = np.clip(e[inside], self.lo, self.hi)
        gap_s = np.concatenate([[self.lo], e])
        gap_e = np.concatenate([s, [self.hi]])
        length = gap_e - gap_s
        for i in np.argsort(-length, kind="stable")[:k]:
            if length[i] > 0:
                gaps.append([self.host_label(int(gap_s[i])),
                             float(length[i]) / 1e9])
        return gaps


def of(view) -> Optional[Program]:
    """The program's names in the view's trace (None without a trace),
    read from the run's ``.xplane.pb`` once and kept on the view."""
    if view.trace is None:
        return None
    program = getattr(view, "program", None)
    if program is None:
        import harness
        path = tr.find_xplane(str(harness.TRACE_DIR))
        program = Program(view.trace, load(path, sorted(view.trace.raw.ops)))
        view.program = program
    return program


def traced_solves(view) -> List[Tuple[int, int]]:
    """The ``solve`` annotations of the traced solves, if each traced
    solve has one."""
    traced = [s for s in view.host.get("solves", ()) if s["traced"]]
    spans = view.trace.spans("solve")
    return spans if traced and len(spans) == len(traced) else []


def lane_steps(view) -> int:
    """Lane-steps the traced solves offered on one chip."""
    return sum(s["rounds"] for s in view.host["solves"] if s["traced"]) \
        * view.host["steps"] * view.host["lanes_per_chip"]


def traced_rounds(view) -> int:
    return sum(s["rounds"] for s in view.host["solves"] if s["traced"])
