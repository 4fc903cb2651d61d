"""Tracing a short sub-window with jax's profiler, and putting the host's
monotonic clock and the profiler's clock on one axis."""

import dataclasses
import os
import shutil
import time
from typing import List, Optional, Tuple

from benchmarks.harness import trace as tr

SYNC = "bench/clock_sync"


class Profiled:
    """``start()`` ... ``stop()`` around the sub-window; ``load()`` reads the
    trace back and shifts it onto ``time.monotonic`` seconds, so device
    operations, idle gaps and the program's spans share one clock."""

    def __init__(self, directory: str):
        self.directory = directory
        self.window: Optional[Tuple[float, float]] = None   # monotonic s
        self.stall: Optional[Tuple[float, float]] = None    # start() .. stop() returned
        self._sync: List[float] = []

    def _mark(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(SYNC):
            self._sync.append(time.monotonic())
            time.sleep(0.0005)

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no per-call Python events
        options.host_tracer_level = 2       # TraceAnnotations are kept
        self._t_called = time.monotonic()
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._mark()
        self._t_started = time.monotonic()

    def stop(self) -> None:
        import jax
        self._mark()
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        self.window = (self._t_started, t_stop)
        self.stall = (self._t_called, time.monotonic())

    def load(self, host_spans: List[tr.Span]) -> tr.Trace:
        """The trace with every time moved to monotonic seconds, the
        program's own ``host_spans`` (already monotonic) added, and the
        traced window set."""
        trace = tr.read_xplane(tr.newest_xplane(self.directory))
        marks = sorted(s.start for s in trace.spans
                       if s.name == SYNC[len("bench/"):])
        if not marks:
            raise RuntimeError("the trace holds no clock_sync annotation")
        # first mark of the trace <-> first stamp taken inside it
        shift = self._sync[0] - marks[0]
        trace.ops = [dataclasses.replace(o, start=o.start + shift)
                     for o in trace.ops]
        trace.spans = [dataclasses.replace(s, start=s.start + shift)
                       for s in trace.spans] + list(host_spans)
        trace.window = self.window
        return trace
