"""Whose idle is it. Readers of what the program says about time in which
nothing ran: the serve loop's no-work wait (``serve/idle``), the
interpreter's collections (``host/gc``), and ``ahead`` / ``starved`` on the
spans of a step dispatched while the one before was in flight.

The functions over events and gaps are pure (``program_events.Event`` lists,
intervals in monotonic seconds), so tests feed them hand-built ones; the four
``read_*`` take a run's observations and are what
``metrics/<name>.py`` and its ``tick_`` twin call. Each returns None where
there is nothing to read: no profiled sub-window with a device's plane (a run
on the CPU gives no number under these names, whatever its spans say), a
program that has no such span (its registry of trace names lacks it), no step
dispatched ahead. One sweep or one pass each.
"""

from typing import List, Optional, Sequence, Tuple

from benchmarks.harness import program_events as pe
from benchmarks.harness import trace as tr

IDLE = "serve/idle"
GC = "host/gc"

Interval = Tuple[float, float]


# --- pure, over events and gaps ----------------------------------------------

def idle_with_work_seconds(gaps: Sequence[Interval],
                           evs: Sequence[pe.Event]) -> float:
    """Seconds of the idle ``gaps`` whose midpoint lies in no ``serve/idle``
    span of ``evs``: the device was idle and the loop was not waiting for
    work, so the program answers for them. The pieces of one stretch tile
    (one's end is the next's start, ends inclusive), so a gap that straddles
    a cut is held by one of them."""
    idle = [e for e in evs if e.name == IDLE]
    names = tr.spans_at(idle, [(a + b) / 2 for a, b in gaps])
    return sum(b - a for (a, b), name in zip(gaps, names) if name != IDLE)


def step_spans(evs: Sequence[pe.Event]) -> List[pe.Event]:
    """One span for each tick that dispatched a step: its
    ``serve/step_decode`` where it decoded (the one with a ``batch``: a tick
    that only collected the last step stamps one without), else its last
    ``serve/prefill_chunk``. These carry ``ahead`` and ``starved``."""
    out = []
    for _, spans in sorted(pe.by_tick(evs).items()):
        decodes = [e for e in spans if e.name == pe.STEP_DECODE
                   and e.arg("batch") is not None]
        chunks = [e for e in spans if e.name == pe.PREFILL_CHUNK]
        if decodes:
            out.append(decodes[-1])
        elif chunks:
            out.append(max(chunks, key=lambda e: e.start))
    return out


def _share_of_ones(said: Sequence[int]) -> Optional[float]:
    return 100.0 * sum(1 for v in said if v == 1) / len(said) if said else None


def ahead_share(steps: Sequence[pe.Event]) -> Optional[float]:
    """Steps dispatched while the one before was pending (``ahead`` 1) over
    all steps that say, in percent; None without any."""
    return _share_of_ones([e.arg("ahead") for e in steps
                           if e.arg("ahead") is not None])


def starved_share(steps: Sequence[pe.Event]) -> Optional[float]:
    """Of the steps dispatched ahead that say whether the device had run dry
    (``starved``), the share that found it so, in percent; None where no
    step was dispatched ahead or none says (an engine without the counter)."""
    return _share_of_ones([e.arg("starved") for e in steps
                           if e.arg("ahead") == 1
                           and e.arg("starved") is not None])


def gc_pause_max_ms(evs: Sequence[pe.Event]) -> float:
    """The longest ``host/gc`` span of ``evs`` in milliseconds; 0.0 where
    there is none (the short collections of the youngest generation are
    counted on the spans, not emitted)."""
    return 1e3 * max((e.dur for e in evs if e.name == GC), default=0.0)


# --- over a run's observations -------------------------------------------------

def program_has(name: str) -> bool:
    """Does the program under test declare the span ``name``
    (``telemetry/names.py``, the one place a trace name is declared)? A
    cell whose loop never idles emits no ``serve/idle``, so the events
    cannot say whether the program has it."""
    try:
        from deepspeed_tpu.telemetry.names import TRACE_NAMES
    except ImportError:
        return False
    return name in TRACE_NAMES


def on_a_device(obs) -> bool:
    """Was a sub-window of this run profiled on a device that has a plane in
    the trace (a TPU)? On the CPU nothing is reported."""
    return obs.trace is not None and bool(obs.trace.devices)


def windowed(obs) -> List[pe.Event]:
    """The program's spans, any thread, inside the measured window and
    outside the profiler's start and stop."""
    return pe.inside(pe.events(), obs.window, obs.outside_stall)


def read_idle_with_work_share(obs) -> Optional[float]:
    """Idle seconds of the traced sub-window that no ``serve/idle`` holds,
    over the sub-window's seconds, in percent."""
    if not on_a_device(obs) or not program_has(IDLE):
        return None
    a, b = obs.trace.window
    if b <= a:
        return None
    gaps = tr.idle_gaps(obs.trace, obs.trace.window, obs.trace.devices[0])
    return 100.0 * idle_with_work_seconds(gaps, pe.events()) / (b - a)


def read_step_starved_share(obs) -> Optional[float]:
    if not on_a_device(obs):
        return None
    return starved_share(step_spans(windowed(obs)))


def read_step_ahead_share(obs) -> Optional[float]:
    if not on_a_device(obs):
        return None
    return ahead_share(step_spans(windowed(obs)))


def read_host_gc_pause_max_ms(obs) -> Optional[float]:
    if not on_a_device(obs) or not program_has(GC):
        return None
    a, b = obs.window
    return gc_pause_max_ms([e for e in pe.events() if a <= e.start <= b])
