"""Names and scopes from a run's ``.xplane.pb``: what ``trace.read_xplane``
leaves behind. jax's ``ProfileData`` gives an event's name, start and
duration, not the stats of its metadata, and that is where XLA puts an
operation's ``tf_op``: the ``jax.named_scope`` path it was traced under
(``jit(decode_step_g)/attn/paged/...``). So this module reads the file's
protobuf wire format itself (the ``XSpace`` message of
tsl/profiler/protobuf/xplane.proto; only the fields named below), with
nothing but the standard library.

A kernel's ``name=`` arrives as the HLO instruction's name (``%flash_fwd.32 =
... custom-call(...)``), so ``kernel_of`` reads it from there.

Times are seconds on the profiler's clock, as ``read_xplane`` has them before
``profile.Profiled.load`` shifts them; ``shift_to_monotonic`` recovers that
shift from the ``clock_sync`` mark both hold.
"""

import bisect
import dataclasses
import functools
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks.harness import trace as tr

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int) -> Tuple[int, int]:
    """(value, index after it) of the varint that starts at ``buf[i]``."""
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == _VARINT:
            value, i = _varint(buf, i)
        elif kind == _BYTES:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (_FIXED64, _FIXED32):
            size = 8 if kind == _FIXED64 else 4
            value = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield number, kind, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


@dataclasses.dataclass(frozen=True)
class NamedOp(tr.Op):
    scope: str = ""        # the operation's tf_op: its named_scope path


@dataclasses.dataclass
class Names:
    ops: List[NamedOp]               # leaf operations of every device plane
    host: List[tr.Span]              # every event of the host planes' lines
    devices: List[int]


def _map_entry(view) -> Tuple[int, object]:
    key, value = 0, b""
    for number, _, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(view, want_stats: Sequence[str]):
    """(name, lines, event metadata, stat names) of one XPlane; an event's
    metadata is (name, {stat name: text}) for the stats in ``want_stats``."""
    name, lines, raw_meta, stat_names = "", [], {}, {}
    for number, _, v in _fields(view):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            raw_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            for n2, _, v2 in _fields(value):
                if n2 == 2:
                    stat_names[key] = _text(v2)
    wanted = {k for k, n in stat_names.items() if n in want_stats}
    meta = {}
    for key, value in raw_meta.items():
        ev_name, stats = "", {}
        for number, _, v in _fields(value):
            if number == 2:
                ev_name = _text(v)
            elif number == 5 and wanted:
                stat_id, text = 0, None
                for n2, _, v2 in _fields(v):
                    if n2 == 1:
                        stat_id = v2
                    elif n2 == 5:
                        text = _text(v2)
                    elif n2 == 7:                 # a reference to a name
                        text = stat_names.get(v2, "")
                if stat_id in wanted and text is not None:
                    stats[stat_names[stat_id]] = text
        meta[key] = (ev_name, stats)
    return name, lines, meta


def _line(view):
    """(line name, [(metadata id, start s, duration s)])."""
    name, t0_ns, events = "", 0, []
    for number, _, v in _fields(view):
        if number == 2:
            name = _text(v)
        elif number == 3:
            t0_ns = v
        elif number == 4:
            events.append(v)
    out = []
    for ev in events:
        meta_id = offset_ps = dur_ps = 0
        for number, _, v in _fields(ev):
            if number == 1:
                meta_id = v
            elif number == 2:
                offset_ps = v
            elif number == 3:
                dur_ps = v
        out.append((meta_id, t0_ns * 1e-9 + offset_ps * 1e-12,
                    dur_ps * 1e-12))
    return name, out


@functools.lru_cache(maxsize=2)     # several metrics of a run read one file
def read(path: str) -> Names:
    """Device planes' "XLA Ops" lines as ``NamedOp``s (enclosing operations
    dropped and programs tagged as ``read_xplane`` does), every host event as
    a ``Span`` under its full name."""
    with open(path, "rb") as f:
        data = f.read()
    ops: List[NamedOp] = []
    host: List[tr.Span] = []
    devices: List[int] = []
    for number, _, plane in _fields(data):
        if number != 1:
            continue
        name, lines, meta = _plane(plane, ("tf_op",))
        if name.startswith("/device:TPU:"):
            device = int(name.rsplit(":", 1)[1])
            modules, raw = [], []
            for line in lines:
                line_name, events = _line(line)
                if line_name == "XLA Modules":
                    modules = sorted(
                        (start, start + dur, meta[m][0].split("(")[0])
                        for m, start, dur in events)
                elif line_name == "XLA Ops":
                    for m, start, dur in events:
                        text, stats = meta[m]
                        op_name, kind = tr.parse_hlo(text)
                        raw.append(NamedOp(op_name, start, dur, device, "",
                                           text, kind, False,
                                           stats.get("tf_op", "")))
            if not raw:
                continue
            devices.append(device)
            j = 0
            for op in sorted(tr.leaf_ops(raw), key=lambda o: o.start):
                while j < len(modules) and modules[j][1] < op.start:
                    j += 1
                inside = (j < len(modules)
                          and modules[j][0] <= op.start <= modules[j][1])
                ops.append(dataclasses.replace(
                    op, program=modules[j][2] if inside else ""))
        elif name.startswith("/host:"):
            for line in lines:
                _, events = _line(line)
                host += [tr.Span(meta[m][0], start, dur)
                         for m, start, dur in events if m in meta]
    return Names(ops, host, sorted(devices))


def of_run(obs) -> Optional[Names]:
    """The names of the trace this run's profiler wrote
    (``<root>/.bench_trace/<cell>``); None for an untraced run."""
    if obs.trace is None:
        return None
    try:
        path = tr.newest_xplane(str(obs.cell.root / ".bench_trace"
                                    / obs.cell.name))
    except FileNotFoundError:
        return None
    return read(path)


def shift_to_monotonic(names: Names, trace: tr.Trace) -> Optional[float]:
    """Seconds to add to a time of ``names`` to land on the clock of
    ``trace`` (the host's monotonic clock, after ``Profiled.load``): the
    first ``clock_sync`` mark, which both hold, tied once."""
    raw = sorted(s.start for s in names.host if s.name == "bench/clock_sync")
    tied = sorted(s.start for s in trace.spans if s.name == "clock_sync")
    return tied[0] - raw[0] if raw and tied else None


_WRAPPED = re.compile(r"^(?:(?:jvp|transpose|vmap|remat|checkpoint)_)*(.+?)_*$")


def kernel_of(op: tr.Op) -> str:
    """A Pallas call's ``name=``: the HLO instruction is called
    ``<name>.<n>`` (``flash_fwd.32``), and where the call was traced under a
    transformation of its own the name is wrapped in it
    (``transpose(jvp(flash_bwd_dq))`` arrives as
    ``transpose_jvp_flash_bwd_dq__``), which is taken off. "" for any other
    operation."""
    if op.kind != "custom-call" or "tpu_custom_call" not in op.detail:
        return ""
    name = op.name.rsplit(".", 1)[0] if "." in op.name else op.name
    return _WRAPPED.match(name).group(1)


def in_window(ops: Sequence[NamedOp], window, shift: float = 0.0):
    """Operations that ran wholly inside ``window`` once ``shift`` is added
    to their times."""
    a, b = window
    return [o for o in ops if a <= o.start + shift and o.end + shift <= b]


def clock_skew_s(names: Names, ring: Sequence, shift: float, window):
    """Largest difference, over the ring's spans inside ``window``, between
    a span's start on the ring and the start of the nearest span of the same
    name in the profiler's trace, moved by ``shift``. None when the
    profiler's trace holds none of the ring's names: a program whose spans
    are not mirrored."""
    mirrored: Dict[str, List[float]] = {}
    for s in names.host:
        mirrored.setdefault(s.name, []).append(s.start + shift)
    for starts in mirrored.values():
        starts.sort()
    a, b = window
    worst = None
    for e in ring:
        starts = mirrored.get(e.name)
        if not starts or not (a <= e.start and e.end <= b):
            continue
        i = bisect.bisect_left(starts, e.start)
        near = min(abs(starts[k] - e.start)
                   for k in (i - 1, i) if 0 <= k < len(starts))
        worst = near if worst is None else max(worst, near)
    return worst
