"""dstrace — always-on structured span tracing.

The telemetry substrate that unifies the repo's five observability islands
(timer registry, CommsLogger, monitor fan-out, serving metrics, resilience
diagnostics) into ONE host-clock event stream: bounded ring buffer of span /
instant events with monotonic ids and explicit step / request correlation
keys, exported as Chrome-trace JSON (Perfetto-loadable) plus an in-process
summary API.

Design constraints (all load-bearing):

- **Never a host sync.** Emission reads ``time.monotonic()`` and appends a
  tuple — no ``float()`` on device arrays, no transfers, no wait for the
  device. The one jax call is the profiler mirror below, which touches no
  array. The emit helpers are registered DS002 hot paths, so the linter
  *proves* the tracer cannot regrow a sync (``tools/dslint/hotpath.py``).
- **One clock with the device trace.** While tracing is on, every ``with
  tracer.span(...)`` also opens a ``jax.profiler.TraceAnnotation`` of the
  same name, so whenever a profiler trace is being taken (by a benchmark or
  by ``engine.start_profile_trace()``) the program's spans sit in that trace
  beside the device operations; when none is, the annotation costs a flag
  test. The step-level spans of ``STEP_SPANS`` open a
  ``StepTraceAnnotation`` numbered by their tick or step. Enabled means
  mirrored: there is no second switch. With tracing off ``span()`` returns
  the shared no-op and jax is never imported from here. Retro-emitted
  events (``complete``) have no live extent and stay on the ring alone.
- **The interpreter's pauses are spans too.** While enabled through
  ``configure`` (or ``DSTPU_TRACE``) the tracer holds one entry in
  ``gc.callbacks`` and stamps every collection as ``host/gc`` on a track of
  its own (``HOST_GC_TID``); switched off, the entry is removed.
- **Lock-free emit.** ``deque.append`` and ``itertools.count.__next__`` are
  GIL-atomic; the only lock guards export/reconfiguration. Producers on the
  serve loop, prefetch worker, watchdog monitor, and main thread never
  contend.
- **Signal-safe instants.** ``instant(..., fanout=False)`` does nothing but
  an append — no I/O, no locks, no allocation beyond one tuple — so the
  resilience SIGTERM handler can leave a breadcrumb (DS005-clean).
- **Bounded memory.** The ring holds ``capacity`` events (oldest evicted);
  a long-running server traces forever at a fixed footprint, and the
  resilience diagnostic bundles embed ``tail(seconds)`` slices of it.

Activation: ``configure_tracing(enabled=True)``, or set ``DSTPU_TRACE=path``
in the environment — tracing starts at first use and the trace is dumped to
``path`` at interpreter exit (plus wherever ``engine.dump_trace`` is called).
"""

import atexit
import collections
import gc
import itertools
import json
import os
import socket
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

TRACE_ENV = "DSTPU_TRACE"
TRACE_CAPACITY_ENV = "DSTPU_TRACE_CAPACITY"
DEFAULT_CAPACITY = 65536

#: env fallbacks for the process-identity header (``set_process_identity``
#: is the programmatic form — ``comm.mesh.init_distributed`` stamps it at
#: rendezvous, which covers every MULTICHIP worker; the env form covers
#: launchers that know the rank before the process does)
TRACE_RANK_ENV = "DSTPU_TRACE_RANK"
TRACE_WORLD_ENV = "DSTPU_TRACE_WORLD"

#: synthetic tid range for per-request serving tracks — renders one Perfetto
#: track per request uid. Real thread idents are pointer-sized (far above
#: this window), so [BASE, BASE + SPAN) never collides with a live thread.
REQUEST_TID_BASE = 1_000_000
REQUEST_TID_SPAN = 10_000_000

#: synthetic track for the comm-compression ``comm/overlap`` bucket spans
#: (below the request window; same no-collision argument). Its own track is
#: the contract ``dstpu plan`` relies on: off-main-track spans attribute as
#: overlapped work — the prefetch-worker treatment — never as step cost.
COMM_OVERLAP_TID = 900_000

#: synthetic track for the interpreter's collections (``host/gc``). A
#: collection holds the interpreter lock whichever thread set it off (a
#: caller's or a load generator's as much as the serve loop's), so it stalls
#: every thread and belongs to none: one track, the thread it ran on an arg.
HOST_GC_TID = 900_001

#: a collection of the youngest generation shorter than this is counted
#: (``gen0``, ``gen0_s`` on the next ``host/gc`` emitted) and not emitted: a
#: busy process makes thousands a second, tens of microseconds each
GC_GEN0_EMIT_S = 1e-3


def request_tid(uid: int) -> int:
    """Synthetic per-request track id (stable for a given uid)."""
    return REQUEST_TID_BASE + (int(uid) % REQUEST_TID_SPAN)

# event tuple layout: (eid, name, cat, ph, ts_s, dur_s, tid, args_or_None)
_EID, _NAME, _CAT, _PH, _TS, _DUR, _TID, _ARGS = range(8)


def _quantile(sorted_vals, q: float) -> float:
    """Exact sample quantile over pre-sorted values — the repo-wide rule
    (serving ``_LatencyStat.quantile`` / ``attribution.quantile``): the
    value at index ``min(int(q*n), n-1)``. The step-time attribution of
    ``dstpu plan`` consumes these, so the rule must not drift per caller."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(q * len(sorted_vals)), len(sorted_vals) - 1)]


class _NoopSpan:
    """Shared do-nothing context — THE fast path when tracing is off (one
    attribute read + one identity return per ``span()`` call)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def note(self, **args) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


def _gc_hook(tracer_ref):
    """The entry a tracer keeps in ``gc.callbacks`` while it is enabled. It
    holds the tracer weakly: the list outlives every tracer, and a strong
    reference from it would too."""
    def on_gc(phase, info):
        tracer = tracer_ref()
        if tracer is not None:
            tracer._on_gc(phase, info)
    return on_gc


def _drop_gc_hook(hook) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:
        pass

#: step-level spans -> the arg that numbers them: these mirror into the
#: profiler as a ``StepTraceAnnotation`` (``step_num``), which is what the
#: profiler's per-step analysis and the device's "Steps" line key on
STEP_SPANS = {"serve/engine_step": "tick", "engine/train_step": "step"}

_annotations = None     # (TraceAnnotation, StepTraceAnnotation), on first use


def _profiler_annotation(name: str, args):
    """The profiler's twin of one span. jax is imported here, on the first
    span opened with tracing on, and never when tracing is off."""
    global _annotations
    if _annotations is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _annotations = (TraceAnnotation, StepTraceAnnotation)
    key = STEP_SPANS.get(name)
    if key is not None and args and key in args:
        return _annotations[1](name, step_num=args[key])
    return _annotations[0](name)


class _Span:
    """A live span: enter stamps t0, exit appends one complete ("X") event.
    Nesting falls out of Chrome-trace semantics — same-thread spans nest by
    ts/dur containment, which the with-statement guarantees. The profiler's
    annotation starts when it is made. It is made after the ring's start
    stamp and left before the ring's end stamp, so whatever the two calls
    into the profiler cost (the thread may lose the interpreter lock there
    for milliseconds) lies inside the span and not in a hole before it; the
    two starts are microseconds apart (the benchmark's
    ``trace_clock_skew_us``)."""
    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_mirror")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = 0.0
        self._mirror = None

    def __enter__(self):
        self._t0 = time.monotonic()
        self._mirror = _profiler_annotation(self._name, self._args)
        self._mirror.__enter__()
        return self

    def note(self, **args) -> None:
        """Add args that are known only inside the span (``with
        tracer.span(...) as sp: ...; sp.note(rebuilt=True)``)."""
        self._args = dict(self._args or (), **args)

    def __exit__(self, exc_type, exc, tb):
        self._mirror.__exit__(exc_type, exc, tb)
        t0 = self._t0
        self._tracer._emit(self._name, self._cat, "X", t0,
                           time.monotonic() - t0,
                           threading.get_ident(), self._args)
        return False


class Tracer:
    """Thread-safe bounded span tracer with Chrome-trace export."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._events: collections.deque = collections.deque(
            maxlen=max(int(capacity), 16))
        self._ids = itertools.count(1)        # monotonic event ids
        self._epoch = time.monotonic()        # export ts origin
        self._lock = threading.Lock()         # export/config only, never emit
        self._cleared = 0                     # events discarded by clear()
        self._sink: Optional[Callable[[str, int], None]] = None
        # the interpreter's collections (``host/gc``): this tracer's entry in
        # ``gc.callbacks`` (there while enabled through ``configure``), the
        # start of the collection under way, and the short young collections
        # counted since the last one emitted
        self._gc_hook: Optional[Callable] = None
        self._gc_t0 = 0.0
        self._gc_gen0 = 0
        self._gc_gen0_s = 0.0
        # process identity for cross-rank merge (``dstpu trace merge``):
        # rank/world default from env, re-stampable at rendezvous time
        try:
            self._rank = int(os.environ.get(TRACE_RANK_ENV, 0))
            self._world = int(os.environ.get(TRACE_WORLD_ENV, 1))
        except ValueError:
            self._rank, self._world = 0, 1

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def configure(self, enabled: Optional[bool] = None,
                  capacity: Optional[int] = None) -> "Tracer":
        with self._lock:
            if capacity is not None and capacity != self._events.maxlen:
                old = self._events
                new = collections.deque(old, maxlen=max(int(capacity), 16))
                self._events = new
                # emit is lock-free by design, so a producer may have
                # appended to the old deque between the copy and the swap —
                # carry those over (the remaining loss window is a single
                # concurrent emit's attribute-load-to-append gap)
                last = max((e[_EID] for e in new), default=0)
                new.extend(e for e in list(old) if e[_EID] > last)
            if enabled is not None:
                self.enabled = bool(enabled)
                self._watch_gc(self.enabled)
        return self

    def _watch_gc(self, on: bool) -> None:
        """Hold one entry in ``gc.callbacks`` while tracing is on, none
        while it is off (the cost of ``host/gc`` with tracing off is nil)."""
        if on == (self._gc_hook in gc.callbacks):
            return
        if not on:
            _drop_gc_hook(self._gc_hook)
            self._gc_t0, self._gc_gen0, self._gc_gen0_s = 0.0, 0, 0.0
            return
        if self._gc_hook is None:
            self._gc_hook = _gc_hook(weakref.ref(self))
            weakref.finalize(self, _drop_gc_hook, self._gc_hook)
        gc.callbacks.append(self._gc_hook)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` entry: a ``host/gc`` span from a collection's
        "start" to its "stop", on the ring alone (retro: the profiler's
        annotation would land on whichever thread the collection ran on, and
        its first use imports jax, here inside a collection). Collections do
        not nest and run under the interpreter lock, so one start stamp
        serves. Same contract as ``_emit``: a clock read and an append."""
        now = time.monotonic()
        if phase == "start":
            self._gc_t0 = now
            return
        t0, generation = self._gc_t0, info.get("generation", -1)
        if not t0:
            return
        self._gc_t0 = 0.0
        dur = now - t0
        if generation == 0 and dur < GC_GEN0_EMIT_S:
            self._gc_gen0 += 1
            self._gc_gen0_s += dur
            return
        gen0, gen0_s = self._gc_gen0, self._gc_gen0_s
        self._gc_gen0, self._gc_gen0_s = 0, 0.0
        self.complete("host/gc", dur, cat="host", end_ts=now,
                      tid=HOST_GC_TID, generation=generation,
                      collected=info.get("collected", 0),
                      thread=threading.get_ident(), gen0=gen0,
                      gen0_s=round(gen0_s, 6))

    @property
    def capacity(self) -> int:
        return self._events.maxlen

    def set_process_identity(self, rank: int, world: int) -> None:
        """Stamp this process's rank/world into every future dump header
        (``comm.mesh.init_distributed`` calls this at rendezvous — config
        time, never the hot path). The header is what ``dstpu trace merge``
        joins per-rank dumps on; without it a dump merges as rank 0 of 1."""
        self._rank = int(rank)
        self._world = int(world)

    def process_identity(self) -> Dict[str, Any]:
        """The dump header: who emitted this trace and a FRESH monotonic↔
        wall anchor pair (same instant, both clocks) so a merger can place
        this dump's monotonic timeline on the shared wall clock. Stamped at
        dump time — anchors age badly; a dump-time pair bounds NTP drift to
        the run's tail, not its whole life."""
        return {
            "rank": self._rank,
            "world": self._world,
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            # one anchor pair, read back-to-back: wall_s - monotonic_s maps
            # any event ts (epoch-relative monotonic) onto the wall clock
            "monotonic_s": time.monotonic(),
            "wall_s": time.time(),
            "epoch_monotonic_s": self._epoch,
        }

    def clear(self) -> None:
        with self._lock:
            # cleared events are not ring evictions: account for them so
            # dropped() stays exact across clear()
            self._cleared += len(self._events)
            self._events.clear()

    def attach_sink(self, fn: Callable[[str, int], None]) -> None:
        """Attach the instant-event fan-out hook (``fn(name, step)``) — the
        monitor's ``events`` sink, so guard trips / chaos injections land in
        TensorBoard/CSV alongside gauges. One sink; last attach wins."""
        self._sink = fn

    def detach_sink(self) -> None:
        self._sink = None

    # ------------------------------------------------------------------
    # emission (registered DS002 hot path: must never host-sync)
    # ------------------------------------------------------------------
    def _emit(self, name, cat, ph, ts, dur, tid, args) -> None:
        self._events.append(
            (next(self._ids), name, cat, ph, ts, dur, tid, args))

    def span(self, name: str, cat: str = "host", **args):
        """``with tracer.span("engine/dispatch", step=n): ...`` — a complete
        event on the current thread. Returns a shared no-op context when
        tracing is off (the fast path every instrumented call site relies
        on)."""
        if not self.enabled:
            return _NOOP_SPAN
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "event",
                step: Optional[int] = None, fanout: bool = True,
                tid: Optional[int] = None, **args) -> None:
        """A zero-duration marker (guard trip, chaos injection, preemption
        signal). ``step`` is the correlation key; when present and
        ``fanout`` is True the attached monitor sink also receives it.
        ``fanout=False`` is the signal-handler-safe form: append only, no
        sink, no I/O, no locks. ``tid`` overrides the track (per-request
        serving tracks)."""
        if not self.enabled:
            return
        if step is not None:
            args["step"] = step
        self._emit(name, cat, "i", time.monotonic(), 0.0,
                   tid if tid is not None else threading.get_ident(),
                   args or None)
        sink = self._sink
        if fanout and sink is not None and step is not None:
            try:
                sink(name, step)
            except Exception:
                logger.exception("dstrace: instant sink failed")

    def counter(self, name: str, cat: str = "mem",
                tid: Optional[int] = None, **series) -> None:
        """A Chrome-trace counter sample (``"ph":"C"``): ``series`` maps
        series label -> numeric value, rendered by Perfetto as a stacked
        counter track time-aligned with the spans (the dsmem HBM/RSS/KV
        watermark tracks). Same hot-path contract as ``instant``: one
        append, no locks, no I/O, no device touch."""
        if not self.enabled or not series:
            return
        self._emit(name, cat, "C", time.monotonic(), 0.0,
                   tid if tid is not None else threading.get_ident(),
                   series)

    def complete(self, name: str, dur_s: float, cat: str = "host",
                 end_ts: Optional[float] = None, tid: Optional[int] = None,
                 **args) -> None:
        """Record a span retroactively from a measured duration (the async
        drain's reconciled step window, serving request phases rebuilt from
        lifecycle timestamps). ``end_ts`` is on the tracer clock
        (``time.monotonic``); defaults to now. ``tid`` overrides the track
        (per-request serving tracks use ``REQUEST_TID_BASE + uid``)."""
        if not self.enabled:
            return
        if dur_s < 0.0:
            dur_s = 0.0
        end = time.monotonic() if end_ts is None else end_ts
        self._emit(name, cat, "X", end - dur_s, dur_s,
                   tid if tid is not None else threading.get_ident(),
                   args or None)

    # ------------------------------------------------------------------
    # read side
    # ------------------------------------------------------------------
    def events_snapshot(self) -> List[Tuple]:
        with self._lock:
            return list(self._events)

    def tail(self, seconds: float) -> List[Tuple]:
        """Events whose END falls inside the last ``seconds`` — the slice
        resilience diagnostic bundles embed ("what happened in the 30s
        before the guard quarantined")."""
        cutoff = time.monotonic() - max(float(seconds), 0.0)
        return [e for e in self.events_snapshot()
                if (e[_TS] + e[_DUR]) >= cutoff]

    def dropped(self) -> int:
        """Events evicted from the ring so far (monotonic ids make the count
        exact: last id minus retained length minus clear()ed events)."""
        snap = self.events_snapshot()
        if not snap:
            return 0
        last = max(e[_EID] for e in snap)
        return max(0, last - len(snap) - self._cleared)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def to_chrome(self, events: Optional[List[Tuple]] = None) -> Dict[str, Any]:
        """Chrome-trace/Perfetto JSON object format. Span events are "X"
        (complete) with microsecond ts/dur relative to the tracer epoch;
        instants are "i"; thread-name metadata rides along so Perfetto
        tracks are labeled."""
        if events is None:
            events = self.events_snapshot()
        pid = os.getpid()
        thread_names = {t.ident: t.name for t in threading.enumerate()}
        trace_events: List[Dict[str, Any]] = []
        seen_tids: Dict[int, str] = {}
        for eid, name, cat, ph, ts, dur, tid, args in events:
            tid = int(tid)
            if tid not in seen_tids:
                if tid in thread_names:
                    seen_tids[tid] = thread_names[tid]
                elif tid == COMM_OVERLAP_TID:
                    seen_tids[tid] = "comm-overlap"
                elif tid == HOST_GC_TID:
                    seen_tids[tid] = "host-gc"
                elif REQUEST_TID_BASE <= tid < REQUEST_TID_BASE + \
                        REQUEST_TID_SPAN:
                    seen_tids[tid] = f"request-{tid - REQUEST_TID_BASE}"
                else:
                    seen_tids[tid] = f"thread-{tid}"   # exited thread
            ev: Dict[str, Any] = {
                "name": name, "cat": cat, "ph": ph, "pid": pid, "tid": tid,
                "ts": round((ts - self._epoch) * 1e6, 3),
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            elif ph == "i":
                ev["s"] = "t"          # thread-scoped instant
            if ph == "C":
                # counter events: args ARE the series values (adding the
                # event id would draw a bogus monotonically-rising series)
                ev["args"] = dict(args) if args else {}
            else:
                ev["args"] = dict(args, id=eid) if args else {"id": eid}
            trace_events.append(ev)
        identity = self.process_identity()
        proc_label = "deepspeed_tpu" if identity["world"] <= 1 else \
            f"deepspeed_tpu rank{identity['rank']}/{identity['world']}"
        meta = [{"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": proc_label}}]
        meta.extend({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": label}}
                    for tid, label in sorted(seen_tids.items()))
        return {
            "traceEvents": meta + trace_events,
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "monotonic",
                "events": len(events),
                "dropped": self.dropped(),
                "capacity": self._events.maxlen,
                # the cross-rank join key: which process this dump is, and
                # the clock anchor that places it on the shared wall clock
                "process": identity,
            },
        }

    def export_chrome(self, path: Optional[str] = None,
                      tail_s: Optional[float] = None) -> Dict[str, Any]:
        """Build (and optionally write) the Chrome-trace dump. ``tail_s``
        restricts it to the trailing slice — the diagnostic-bundle form."""
        events = self.tail(tail_s) if tail_s is not None else None
        trace = self.to_chrome(events)
        if path:
            d = os.path.dirname(os.path.abspath(path))
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                # args may hold numpy scalars etc. — stringify, never die
                json.dump(trace, f, default=str)
        return trace

    # ------------------------------------------------------------------
    # aggregation
    # ------------------------------------------------------------------
    def summary(self, prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
        """Per-span-name aggregate over the ring's complete events:
        count / total_s / mean_s / max_s / p50_s / p95_s / p99_s.
        ``prefix`` filters span names (e.g. ``"serve/"``; a tuple of
        prefixes matches any — ``str.startswith`` semantics)."""
        buckets: Dict[str, List[float]] = {}
        for e in self.events_snapshot():
            if e[_PH] != "X":
                continue
            name = e[_NAME]
            if prefix and not name.startswith(prefix):
                continue
            buckets.setdefault(name, []).append(e[_DUR])
        out: Dict[str, Dict[str, float]] = {}
        for name, durs in buckets.items():
            durs.sort()
            n = len(durs)
            out[name] = {
                "count": n,
                "total_s": sum(durs),
                "mean_s": sum(durs) / n,
                "max_s": durs[-1],
                "p50_s": _quantile(durs, 0.5),
                "p95_s": _quantile(durs, 0.95),
                "p99_s": _quantile(durs, 0.99),
            }
        return out

    def instant_counts(self, prefix: Optional[str] = None) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events_snapshot():
            if e[_PH] != "i":
                continue
            name = e[_NAME]
            if prefix and not name.startswith(prefix):
                continue
            out[name] = out.get(name, 0) + 1
        return out

    def counter_series(self, prefix: Optional[str] = None
                       ) -> Dict[str, Dict[str, Dict[str, float]]]:
        """Per-counter per-series aggregate over the ring's "C" events:
        ``{counter: {series: {"last", "max", "p95", "p99", "count"}}}`` —
        the read side of the dsmem HBM/RSS/KV tracks (events are
        id-ordered, so "last" is the newest sample; p95/p99 follow the
        shared exact-quantile rule ``_quantile``, same as the serve-plan
        replay's standalone copy, so KV/prefix counter tracks report tails
        rather than just last/max)."""
        values: Dict[str, Dict[str, List[float]]] = {}
        for e in sorted(self.events_snapshot(), key=lambda e: e[_EID]):
            if e[_PH] != "C" or not e[_ARGS]:
                continue
            name = e[_NAME]
            if prefix and not name.startswith(prefix):
                continue
            bucket = values.setdefault(name, {})
            for series, value in e[_ARGS].items():
                try:
                    v = float(value)
                except (TypeError, ValueError):
                    continue
                bucket.setdefault(series, []).append(v)
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        for name, bucket in values.items():
            rows = out.setdefault(name, {})
            for series, vals in bucket.items():
                last = vals[-1]
                vals.sort()
                rows[series] = {"last": last, "max": vals[-1],
                                "p95": _quantile(vals, 0.95),
                                "p99": _quantile(vals, 0.99),
                                "count": len(vals)}
        return out

    def prometheus_lines(self, prefix: Optional[str] = None) -> List[str]:
        """Prometheus exposition of the span aggregates plus counter-track
        gauges (the serving ``/metrics`` endpoint appends these for
        ``serve/*`` and ``mem/*``)."""
        lines: List[str] = []
        summ = self.summary(prefix=prefix)
        if summ:
            lines += ["# HELP dstpu_trace_span_seconds tracer span durations",
                      "# TYPE dstpu_trace_span_seconds summary"]
            for name in sorted(summ):
                s = summ[name]
                for q, key in ((0.5, "p50_s"), (0.95, "p95_s"),
                               (0.99, "p99_s")):
                    lines.append(f'dstpu_trace_span_seconds{{span="{name}",'
                                 f'quantile="{q}"}} {s[key]:.9g}')
                lines.append(f'dstpu_trace_span_seconds_sum{{span="{name}"}} '
                             f'{s["total_s"]:.9g}')
                lines.append(
                    f'dstpu_trace_span_seconds_count{{span="{name}"}} '
                    f'{int(s["count"])}')
        counters = self.counter_series(prefix=prefix)
        if counters:
            lines += ["# HELP dstpu_trace_counter tracer counter tracks "
                      "(last/peak per series)",
                      "# TYPE dstpu_trace_counter gauge"]
            for name in sorted(counters):
                for series in sorted(counters[name]):
                    s = counters[name][series]
                    for stat in ("last", "max", "p95", "p99"):
                        lines.append(
                            f'dstpu_trace_counter{{counter="{name}",'
                            f'series="{series}",stat="{stat}"}} '
                            f'{s[stat]:.9g}')
        return lines


# ---------------------------------------------------------------------------
# process-global tracer
# ---------------------------------------------------------------------------
_tracer: Optional[Tracer] = None
_tracer_guard = threading.Lock()


def _dump_at_exit(tracer: Tracer, path: str) -> None:
    try:
        tracer.export_chrome(path)
        logger.info(f"dstrace: trace written -> {path} "
                    f"(load in https://ui.perfetto.dev)")
    except Exception:
        logger.exception("dstrace: atexit trace dump failed")


def get_tracer() -> Tracer:
    """THE process tracer every instrumented subsystem shares. First call
    honors ``DSTPU_TRACE=path`` (enable + dump at exit) and
    ``DSTPU_TRACE_CAPACITY``."""
    global _tracer
    t = _tracer
    if t is not None:
        return t
    with _tracer_guard:
        if _tracer is None:
            try:
                cap = int(os.environ.get(TRACE_CAPACITY_ENV,
                                         DEFAULT_CAPACITY))
            except ValueError:
                cap = DEFAULT_CAPACITY
            t = Tracer(capacity=cap)
            path = os.environ.get(TRACE_ENV)
            if path:
                t.configure(enabled=True)
                atexit.register(_dump_at_exit, t, path)
                logger.info(f"dstrace: tracing enabled ({TRACE_ENV}); dump "
                            f"at exit -> {path}")
            _tracer = t
        return _tracer


def configure_tracing(enabled: Optional[bool] = None,
                      capacity: Optional[int] = None) -> Tracer:
    """Convenience front door: ``configure_tracing(enabled=True)``."""
    return get_tracer().configure(enabled=enabled, capacity=capacity)
