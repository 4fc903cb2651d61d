"""From a profiler trace to numbers. Pure functions over plain event lists,
so that tests feed them hand-built events; ``read_xplane`` is a thin adapter
that turns an ``.xplane.pb`` file into those lists with nothing but jax.

Times are seconds on the profiler's clock. An ``Op`` is one event of a
device's "XLA Ops" line; a ``Span`` is a host interval on the same clock.
"""

import bisect
import dataclasses
import glob
import heapq
import itertools
import os
import re
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

Interval = Tuple[float, float]

COLLECTIVE_MARKS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str            # HLO instruction name, e.g. "fusion.22", "copy.168"
    start: float
    dur: float
    device: int = 0
    program: str = ""    # the XLA module it ran in, e.g. "jit_decode_step_g"
    detail: str = ""     # the instruction's text (shapes, operands), where given
    kind: str = ""       # its opcode: "fusion", "custom-call", "copy", ...
    overlapped: bool = False   # from the "Async XLA Ops" line: a transfer or
    #                            collective in flight beside the compute line

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Op]                    # leaf operations of every device
    spans: List[Span]                # host spans on the same clock
    devices: List[int]
    window: Optional[Interval] = None   # the traced window, where known


# --- intervals --------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval],
             holes: Sequence[Interval]) -> List[Interval]:
    """The parts of ``intervals`` that no interval of ``holes`` covers."""
    out = []
    holes = merge(holes)
    for a, b in merge(intervals):
        cur = a
        for ha, hb in holes:
            if hb <= cur:
                continue
            if ha >= b:
                break
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def held_whole(intervals: Iterable[Interval]) -> Callable[[float, float], bool]:
    """A test ``(a, b)``: does one of ``intervals`` hold ``[a, b]`` whole,
    ends inclusive? What ``any(s <= a and b <= e for s, e in intervals)``
    answers, for intervals that may overlap, by one bisection: of the
    intervals that start at or before ``a`` the one that reaches furthest
    decides."""
    ordered = sorted(intervals)
    starts = [s for s, _ in ordered]
    reach = list(itertools.accumulate((e for _, e in ordered), max))

    def test(a: float, b: float) -> bool:
        i = bisect.bisect_right(starts, a)
        return i > 0 and b <= reach[i - 1]
    return test


# --- reductions -------------------------------------------------------------

def leaf_ops(ops: Sequence[Op]) -> List[Op]:
    """Drop every op that encloses another op of the same device: a `while`,
    a `call` or a `conditional` spans its body, and counting both would count
    the body twice."""
    out = []
    by_device: Dict[int, List[Op]] = {}
    for op in ops:
        by_device.setdefault(op.device, []).append(op)
    for device_ops in by_device.values():
        device_ops.sort(key=lambda o: (o.start, -o.dur))
        for i, op in enumerate(device_ops):
            nxt = device_ops[i + 1] if i + 1 < len(device_ops) else None
            encloses = (nxt is not None and nxt.start >= op.start
                        and nxt.end <= op.end and nxt.dur < op.dur)
            if not encloses:
                out.append(op)
    return out


def is_collective(op: Op) -> bool:
    return any(mark in op.name or mark in op.kind for mark in COLLECTIVE_MARKS)


_HLO = re.compile(r"^%(?P<name>\S+) = .*?\s(?P<kind>[a-z][a-z0-9\-]*)\(")


def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an "XLA Ops" event, whose name is the
    instruction's whole text: ``%copy.168 = bf16[...]{...} copy(...)``. A name
    in another form is returned as it is, with no opcode."""
    m = _HLO.match(text)
    return (m.group("name"), m.group("kind")) if m else (text, "")


def busy_by_device(trace: Trace, window: Interval) -> Dict[int, float]:
    """Seconds inside ``window`` in which some operation ran, per device."""
    out = {}
    for device in trace.devices:
        out[device] = total(clip(merge(
            (o.start, o.end) for o in trace.ops
            if o.device == device and not o.overlapped), window))
    return out


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Busy seconds averaged over the devices used."""
    per = busy_by_device(trace, window)
    return sum(per.values()) / max(len(per), 1)


def idle_gaps(trace: Trace, window: Interval, device: int) -> List[Interval]:
    busy = clip(merge((o.start, o.end) for o in trace.ops
                      if o.device == device and not o.overlapped), window)
    return subtract([window], busy)


def span_at(spans: Sequence[Span], t: float) -> str:
    """Name of the shortest host span that holds instant ``t``."""
    return spans_at(spans, [t])[0]


def spans_at(spans: Sequence[Span], instants: Sequence[float]) -> List[str]:
    """For each instant the name of the shortest span that holds it (ends
    inclusive; of equally short ones the first in ``spans``), or
    ``"(no span)"``. One sweep: the instants in rising order over the spans
    by start, the spans that have opened on a heap by (duration, place in
    ``spans``). A span on top that has ended is dropped for good, since no
    later instant can fall in it, so what stays on top is the shortest one
    that holds the instant. Spans that lie wholly before the first or after
    the last instant are never looked at. O((n + m) log m) for n instants and
    m spans, where a scan of the spans for each instant is n x m; the spans
    need not nest."""
    out = ["(no span)"] * len(instants)
    if not out or not spans:
        return out
    lo, hi = min(instants), max(instants)
    opening = sorted((s.start, i) for i, s in enumerate(spans)
                     if s.start <= hi and s.end >= lo)
    heap: List[Tuple[float, int]] = []
    j = 0
    for k in sorted(range(len(instants)), key=instants.__getitem__):
        t = instants[k]
        while j < len(opening) and opening[j][0] <= t:
            i = opening[j][1]
            heapq.heappush(heap, (spans[i].dur, i))
            j += 1
        while heap and spans[heap[0][1]].end < t:
            heapq.heappop(heap)
        if heap:
            out[k] = spans[heap[0][1]].name
    return out


def longest_gaps(trace: Trace, window: Interval, device: int,
                 n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of ``device``, each named by the host span
    its middle falls in."""
    gaps = sorted(idle_gaps(trace, window, device),
                  key=lambda g: g[0] - g[1])[:n]
    names = spans_at(trace.spans, [(a + b) / 2 for a, b in gaps])
    return [(name, b - a) for name, (a, b) in zip(names, gaps)]


def op_seconds(ops: Iterable[Op]) -> Dict[str, float]:
    """Device seconds by ``program/op`` name, summed over events and
    averaged over nothing: a four-chip run's op counts four times."""
    out: Dict[str, float] = {}
    for op in ops:
        key = f"{op.program}/{op.name}" if op.program else op.name
        out[key] = out.get(key, 0.0) + op.dur
    return out


def top_ops(trace: Trace, window: Interval, n: int = 10):
    ops = [o for o in trace.ops if o.start >= window[0] and o.end <= window[1]
           and not o.overlapped]
    per = op_seconds(ops)
    scale = max(len(trace.devices), 1)       # mean over devices
    return sorted(((k, v / scale) for k, v in per.items()),
                  key=lambda kv: -kv[1])[:n]


def exposed_collective_seconds(trace: Trace, window: Interval) -> float:
    """Seconds, averaged over devices, in which a collective operation ran on
    a device and no compute operation ran on it."""
    out = 0.0
    for device in trace.devices:
        mine = [o for o in trace.ops if o.device == device]
        coll = clip(merge((o.start, o.end) for o in mine if is_collective(o)),
                    window)
        compute = merge((o.start, o.end) for o in mine
                        if not is_collective(o) and not o.overlapped)
        out += total(subtract(coll, compute))
    return out / max(len(trace.devices), 1)


# --- the file reader --------------------------------------------------------

def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, span_prefix: str = "bench/") -> Trace:
    """Device planes' "XLA Ops" lines become ``Op``s (enclosing ops dropped,
    each tagged with the "XLA Modules" run it falls in); host events whose
    name starts with ``span_prefix`` become ``Span``s."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    devices: List[int] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.rsplit(":", 1)[1])
            modules = []
            raw = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      e.name.split("(")[0])
                                     for e in line.events)
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    for e in line.events:
                        name, kind = parse_hlo(e.name)
                        raw.append(Op(name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, device, "",
                                      e.name, kind,
                                      line.name == "Async XLA Ops"))
            if not raw:
                continue
            devices.append(device)
            j = 0
            kept = leaf_ops([o for o in raw if not o.overlapped]) + \
                [o for o in raw if o.overlapped]
            for op in sorted(kept, key=lambda o: o.start):
                while j < len(modules) and modules[j][1] < op.start:
                    j += 1
                program = (modules[j][2] if j < len(modules)
                           and modules[j][0] <= op.start <= modules[j][1]
                           else "")
                ops.append(dataclasses.replace(op, program=program))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(Span(e.name[len(span_prefix):],
                                          e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    return Trace(ops, spans, sorted(devices))
