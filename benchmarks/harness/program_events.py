"""The program's own events with their args (``telemetry/tracer.py``'s ring,
monotonic clock), and what several readers select from them: the spans of the
serve loop's thread, the phases of one tick, the idle gaps a phase explains.

``harness/spans.py`` hands the runners names and times only; the counts the
program puts on its spans (``batch``, ``bucket``, ``ctx_tokens_windowed``,
``tick``) are read here. Every function below ``events`` is pure over plain
lists, so tests feed them hand-built events.
"""

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import trace as tr

_NAME, _PH, _TS, _DUR, _TID, _ARGS = 1, 3, 4, 5, 6, 7

TICK = "serve/tick"
STEP_DECODE = "serve/step_decode"
PREFILL_CHUNK = "serve/prefill_chunk"
DECODE_WAIT = "serve/decode_wait"
#: spans that open with a dispatch to the device
DISPATCH = ("serve/decode_dispatch", PREFILL_CHUNK)
#: spans that hold phases: a gap that only these explain, or no span at all
#: (``trace.span_at``'s answer then), is not attributed
CONTAINERS = ("serve/step_decode", "serve/step_prefill", "serve/engine_step",
              "serve/tick", "(no span)")


@dataclasses.dataclass(frozen=True)
class Event:
    """One complete span of the program (monotonic seconds)."""
    name: str
    start: float
    dur: float
    tid: int = 0
    args: Optional[dict] = None

    @property
    def end(self) -> float:
        return self.start + self.dur

    def arg(self, key: str, default=None):
        return (self.args or {}).get(key, default)


def events() -> List[Event]:
    """Every complete span the program's tracer holds, oldest first. Empty
    when the program has no tracer or it is off."""
    try:
        from deepspeed_tpu.telemetry.tracer import get_tracer
    except ImportError:
        return []
    return [Event(e[_NAME], e[_TS], e[_DUR], e[_TID], e[_ARGS])
            for e in get_tracer().events_snapshot() if e[_PH] == "X"]


def inside(evs: Sequence[Event], window: Tuple[float, float],
           keep=lambda t: True) -> List[Event]:
    """Events that lie wholly inside ``window`` and whose two ends pass
    ``keep`` (``obs.outside_stall``: not while the profiler started or
    stopped)."""
    a, b = window
    return [e for e in evs if a <= e.start and e.end <= b
            and keep(e.start) and keep(e.end)]


def loop_thread(evs: Sequence[Event]) -> List[Event]:
    """The spans of the thread that runs the serve loop: the one that emits
    ``serve/tick``. Request spans live on tracks of their own and would
    otherwise hold every instant of a request's life."""
    tids = {e.tid for e in evs if e.name == TICK}
    return [e for e in evs if e.tid in tids]


def by_tick(evs: Sequence[Event]) -> Dict[int, List[Event]]:
    out: Dict[int, List[Event]] = {}
    for e in evs:
        tick = e.arg("tick")
        if tick is not None:
            out.setdefault(int(tick), []).append(e)
    return out


def decode_bubbles(evs: Sequence[Event]) -> List[float]:
    """Per decode tick, the seconds from the end of its ``serve/decode_wait``
    (the device has handed back the tokens and has nothing queued) to the
    start of the next tick's first dispatch span: host time the device sits
    out. Ticks are ordered by their number; a decode tick with no dispatching
    tick after it gives nothing."""
    ticks = by_tick(evs)
    order = sorted(ticks)
    out = []
    for this, nxt in zip(order, order[1:]):
        decode = [e for e in ticks[this] if e.name == STEP_DECODE]
        waits = [e for e in ticks[this] if e.name == DECODE_WAIT]
        starts = [e.start for e in ticks[nxt] if e.name in DISPATCH]
        if decode and waits and starts:
            out.append(min(starts) - max(w.end for w in waits))
    return out


def fill(evs: Sequence[Event], name: str, used: str, padded: str):
    """Sum of ``used`` over sum of ``padded`` over the spans called ``name``
    that carry both, as a share in percent; None without any."""
    pairs = [(e.arg(used), e.arg(padded)) for e in evs if e.name == name]
    pairs = [(u, p) for u, p in pairs if u is not None and p]
    if not pairs:
        return None
    return 100.0 * sum(u for u, _ in pairs) / sum(p for _, p in pairs)


def attributed_idle_share(gaps: Sequence[Tuple[float, float]],
                          evs: Sequence[Event]):
    """Seconds of the idle ``gaps`` whose midpoint lies in a leaf phase span
    (the shortest span of ``evs`` that holds it, ``trace.spans_at``, is not
    one of ``CONTAINERS``) over all idle seconds, in percent; None when
    there is no idle time or no span. ``evs`` may be the spans of the whole
    run: the sweep passes over those that end before the first midpoint or
    start after the last."""
    whole = sum(b - a for a, b in gaps)
    if not whole or not evs:
        return None
    names = tr.spans_at(evs, [(a + b) / 2 for a, b in gaps])
    named = sum(b - a for (a, b), name in zip(gaps, names)
                if name not in CONTAINERS)
    return 100.0 * named / whole
