"""Wall-clock + throughput timers.

TPU-native analog of the reference's ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` at :44, ``ThroughputTimer`` at :199). CUDA events do not
exist here; synchronization is ``jax.block_until_ready`` on a token array, which forces
completion of all previously enqueued XLA work on the device.
"""

import collections
import threading
import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import logger

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"
# async step pipeline: host time spent *dispatching* a step (enqueue only, no
# completion wait) — the gap between launches that latency hiding minimizes.
# True per-step time is reconciled into TRAIN_BATCH_TIMER at each metric drain.
TRAIN_BATCH_DISPATCH_TIMER = "train_batch_dispatch"


def _device_sync():
    try:
        import jax
        # Touching a tiny computation and blocking flushes the async dispatch queue.
        jax.block_until_ready(jax.numpy.zeros(()))
    except Exception:
        pass


class Timer:
    """A single named timer with start/stop/elapsed, mean and total."""

    def __init__(self, name: str, synchronize: bool = True):
        self.name = name
        self.synchronize = synchronize
        self._started = False
        self._ever_started = False
        self._start_time = 0.0
        self._elapsed = 0.0
        self._records: List[float] = []

    def start(self):
        if self._started:
            return
        if self.synchronize:
            _device_sync()
        self._start_time = time.time()
        self._started = True
        self._ever_started = True

    def stop(self, record: bool = True):
        if not self._started:
            return
        if self.synchronize:
            _device_sync()
        delta = time.time() - self._start_time
        self._elapsed += delta
        if record:
            self._records.append(delta)
        self._started = False

    def reset(self):
        self._started = False
        self._elapsed = 0.0
        self._records = []

    def record_external(self, seconds: float, count: int = 1):
        """Fold externally measured wall time into this timer as ``count``
        equal records. The async step pipeline's reconciliation hook: per-step
        start/stop in ``synchronize=False`` mode only sees dispatch time, so
        the engine measures the true drain-to-drain window (whose end is
        anchored by the drain's device_get) and books it here."""
        self._ever_started = True
        seconds = max(float(seconds), 0.0)
        count = max(int(count), 1)
        self._elapsed += seconds
        self._records.extend([seconds / count] * count)

    def elapsed(self, reset: bool = True) -> float:
        """Elapsed seconds since last reset (stops/restarts a running timer)."""
        if not self._ever_started:
            logger.warning(f"timer '{self.name}': elapsed() before any "
                           "start(); returning 0.0")
            return 0.0
        was_started = self._started
        if was_started:
            self.stop(record=False)
        value = self._elapsed
        if reset:
            self._elapsed = 0.0
            self._records = []
        if was_started:
            self.start()
        return value

    def mean(self) -> float:
        if not self._ever_started:
            logger.warning(f"timer '{self.name}': mean() before any start(); "
                           "returning 0.0")
            return 0.0
        return sum(self._records) / len(self._records) if self._records else 0.0


class SynchronizedWallClockTimer:
    """Registry of named timers (reference: utils/timer.py:44).

    ``synchronize=False`` makes every timer measure dispatch time only (no
    device round trip per start/stop) — the engine uses this unless
    ``wall_clock_breakdown`` is on, mirroring the reference's gating of
    EngineTimers; a device sync stalls the host until the step has finished.
    """

    def __init__(self, synchronize: bool = True):
        self.timers: Dict[str, Timer] = {}
        self.synchronize = synchronize

    def __call__(self, name: str) -> Timer:
        if name not in self.timers:
            self.timers[name] = Timer(name, synchronize=self.synchronize)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False) -> None:
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}ms")
        if parts:
            logger.info("time (ms) | " + " | ".join(parts))

    def get_mean(self, names: List[str], normalizer: float = 1.0) -> Dict[str, float]:
        out = {}
        for name in names:
            if name in self.timers:
                out[name] = self.timers[name].mean() * 1000.0 / normalizer
        return out


class ThroughputTimer:
    """samples/sec + TFLOPS reporting (reference: utils/timer.py:199).

    ``flops_per_sample`` may be supplied by the engine (e.g. from the flops profiler /
    XLA cost analysis) to report model TFLOPS at ``steps_per_print`` boundaries.

    Throughput is measured **edge to edge**: the wall clock is read (after a
    device sync) at report-window boundaries only, and the window's samples are
    divided by the full boundary-to-boundary interval. Per-step timing would
    undercount whenever the caller itself syncs between steps (e.g.
    ``float(loss)`` for logging) — the device work would then drain in the
    untimed gap between ``stop()`` and the next ``start()`` and the report
    would only see ~ms dispatch times. Edge-to-edge includes those gaps by
    construction, at one device round trip per window.

    ``synchronize=False`` (async step pipeline): start/stop never touch the
    device and NEVER close a window on their own — only ``mark_edge()``,
    called by the engine right after a metric-ring drain (whose batched
    ``device_get`` already proves the drained steps' device work finished),
    closes windows. Throughput stays honest without any extra sync.
    """

    def __init__(self, batch_size: int, steps_per_output: int = 100,
                 monitor_memory: bool = False, logging_fn=None,
                 synchronize: bool = True):
        self.batch_size = max(1, batch_size)
        self.steps_per_output = steps_per_output
        self.synchronize = synchronize
        self.logging = logging_fn or logger.info
        self.started = False
        self.global_step_count = 0
        self.steps_since_edge = 0
        self.total_elapsed_time = 0.0   # sum over completed report windows
        self._steps_in_total = 0        # steps covered by total_elapsed_time
        self._edge_time: Optional[float] = None
        self._last_report_step = 0
        self.flops_per_sample: Optional[float] = None

    def start(self):
        self.started = True
        if self._edge_time is None:
            if self.synchronize:
                _device_sync()
            self._edge_time = time.time()

    def stop(self, global_step: bool = True, report_speed: bool = True):
        if not self.started:
            return
        self.started = False
        if not global_step:
            return
        self.global_step_count += 1
        self.steps_since_edge += 1
        if self.synchronize and self.steps_per_output and \
                self.global_step_count % self.steps_per_output == 0:
            _device_sync()   # drain device work belonging to this window
            self._close_window(report_speed)

    def mark_edge(self, report_speed: bool = True):
        """Close the current window at a caller-guaranteed completion point
        (the async engine calls this right after its drain's device_get, so
        no device sync happens here). Reports at ``steps_per_output`` cadence
        like the synchronous path."""
        if self.steps_since_edge == 0:
            if self._edge_time is None:
                self._edge_time = time.time()
            return
        report = (report_speed and bool(self.steps_per_output)
                  and self.global_step_count - self._last_report_step
                  >= self.steps_per_output)
        self._close_window(report)

    def _close_window(self, report_speed: bool):
        now = time.time()
        window = max(now - self._edge_time, 1e-9)
        self.total_elapsed_time += window
        self._steps_in_total += self.steps_since_edge
        if report_speed:
            sps = self.batch_size * self.steps_since_edge / window
            msg = (f"epoch step {self.global_step_count}: "
                   f"{sps:.1f} samples/s, batch time "
                   f"{window / self.steps_since_edge * 1000:.1f} ms")
            if self.flops_per_sample:
                msg += f", {sps * self.flops_per_sample / 1e12:.2f} TFLOPS"
            self.logging(msg)
            self._last_report_step = self.global_step_count
        self._edge_time = now
        self.steps_since_edge = 0

    def avg_samples_per_sec(self) -> float:
        """Cumulative samples/sec over completed report windows (falls back to
        the partial current window, without a sync, if none completed yet)."""
        if self._steps_in_total > 0 and self.total_elapsed_time > 0:
            return self.batch_size * self._steps_in_total / self.total_elapsed_time
        if self.steps_since_edge > 0 and self._edge_time is not None:
            partial = max(time.time() - self._edge_time, 1e-9)
            return self.batch_size * self.steps_since_edge / partial
        return 0.0


class RateTracker:
    """Rolling events/sec over a sliding wall-clock window (serving
    throughput gauges: tokens/sec, requests/sec). Thread-safe; no device
    sync — serving rates time host-observed events, not XLA completion."""

    def __init__(self, window_s: float = 30.0):
        self.window_s = window_s
        self._events = collections.deque()   # (monotonic_ts, count)
        self._start = time.monotonic()
        self._lock = threading.Lock()

    def add(self, n: float = 1.0, now: Optional[float] = None):
        now = time.monotonic() if now is None else now
        with self._lock:
            self._events.append((now, n))
            self._prune(now)

    def _prune(self, now: float):
        cutoff = now - self.window_s
        while self._events and self._events[0][0] < cutoff:
            self._events.popleft()

    def rate(self, now: Optional[float] = None) -> float:
        """Events/sec averaged over the full window (0.0 when empty). The
        divisor is the window span — not the oldest-event age, which would
        spike absurdly for a single event right after an idle period — and
        shrinks to the tracker's lifetime while younger than the window."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._prune(now)
            if not self._events:
                return 0.0
            span = max(min(self.window_s, now - self._start), 1e-9)
            return sum(n for _, n in self._events) / span
